"""Runners: one per path of the system, named by a configuration's
``runner`` key.  Each ``run(...)`` sets up, measures one window and
checks what the timed path produced, returning a ``bench.record.Run``."""
