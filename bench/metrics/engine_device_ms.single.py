"""engine_device_ms.single: device time of the engine's XLA modules (the
'engine' row of layers.json) in the traced window, per round of the window."""


def read(run):
    t = run.trace
    if not t or not run.rounds or not t["layers"].get("engine"):
        return None
    return 1e3 * t["layers"]["engine"] / len(run.rounds)
