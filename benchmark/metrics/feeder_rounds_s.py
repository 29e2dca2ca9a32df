"""Feeder rounds per second of window: (delta ``mask_dispatches`` +
delta ``sha_dispatches``) / window seconds.
Layer: cross-session batcher."""


def read(window):
    f = window.counters["feeder"]
    rounds = f["mask_dispatches"] + f["sha_dispatches"]
    return rounds / window.seconds if rounds else None
