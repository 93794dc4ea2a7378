"""The turbo decodes' essential work at the chip's roofline
(bench/roofline_turbo.py, per iteration run), over the summed time of the
Pallas kernels that did it (the BCJR alpha and beta + LLR scans)."""


def read(r):
    t = r["trace"]
    if t.kernel_s <= 0 or not r.get("essential_s"):
        return None
    return 100.0 * r["essential_s"] / t.kernel_s
