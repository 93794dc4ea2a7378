"""What the drivers share: the program's codec spec built from a
configuration file, and the reference's view of the same code."""
from __future__ import annotations

from bench import reference


def polys(config: dict):
    return tuple(int(p, 8) for p in config["code"]["polys_octal"])


def codec_spec(config: dict):
    """The program's CodecSpec for a configuration (soft decisions)."""
    from repro.core.trellis import ConvCode
    from repro.decode import CodecSpec

    code = config["code"]
    return CodecSpec(
        code=ConvCode(int(code["constraint"]), polys(config)),
        metric="soft",
        puncture=code.get("puncture"),
        terminated=bool(code["terminated"]),
    )


def reference_code(config: dict) -> reference.Code:
    return reference.Code(int(config["code"]["constraint"]), polys(config))
