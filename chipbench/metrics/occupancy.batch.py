"""Share (%) of the decode launches' slot lanes that held a live request
in the window (the engine's busy / offered slot-step counters)."""


def read(run):
    busy, offered = run.slot_steps
    return 100.0 * busy / offered if offered else None
