"""Device time outside the Pallas kernels, as a share of the device's busy
time: the layout, padding, gathers and frontier work of kernels/ops.py."""


def read(r):
    t = r["trace"]
    busy = t.kernel_s + t.glue_s
    return 100.0 * t.glue_s / busy if busy > 0 else None
