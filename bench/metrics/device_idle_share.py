"""Per cent of the traced window in which no operation ran on the device:
1 - busy / window, busy being the union of the device's op intervals in a
``jax.profiler`` trace of whole units, averaged over the cell's devices.
No stage session is open while it is traced.
"""
NEEDS = "device_trace"


def read(obs):
    if not obs.device or obs.device["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - obs.device["busy_s"] / obs.device["window_s"])
