"""The decodes' essential work at the chip's roofline (bench/roofline.py),
over the summed time of the Pallas kernels that did it."""


def read(r):
    t = r["trace"]
    if t.kernel_s <= 0 or not r.get("essential_s"):
        return None
    return 100.0 * r["essential_s"] / t.kernel_s
