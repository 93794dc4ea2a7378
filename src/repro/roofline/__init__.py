from repro.roofline.analysis import (
    PEAKS,
    collective_bytes,
    hardware,
    model_flops,
    roofline_report,
    roofline_terms,
)

__all__ = [
    "PEAKS", "collective_bytes", "hardware", "model_flops", "roofline_terms",
    "roofline_report",
]
