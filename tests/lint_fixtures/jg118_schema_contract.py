"""JG118 fixture: a record kind without a REQUIRED core.

``probe`` is declared in ``EVENTS`` but its ``REQUIRED`` entry is empty:
a reader of the stream has no field of a ``probe`` record it can count
on.  ``round`` has its core, so exactly one JG118 finding fires.
"""
EVENTS = ("round", "probe")

REQUIRED = {"round": ("event", "schema"), "probe": ()}
