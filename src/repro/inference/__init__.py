from .fold_norms import fold_norms
