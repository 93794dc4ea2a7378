"""Mean host time of a turbo iteration's jitted call, to its return: the
program's ``turbo.dispatch`` spans.  After each iteration's ``turbo.sync``
the device waits this long for the next iteration's work."""


def read(r):
    spans = [d for n, _, d in r.get("program_spans") or [] if n == "turbo.dispatch"]
    return 1e-6 * sum(spans) / len(spans) if spans else None
