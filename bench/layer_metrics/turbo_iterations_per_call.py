"""Turbo iterations a decode() call runs: the program's ``turbo.iteration``
spans over its ``decode`` spans in the window."""


def read(r):
    names = [n for n, _, _ in r.get("program_spans") or []]
    calls, its = names.count("decode"), names.count("turbo.iteration")
    return its / calls if calls and its else None
