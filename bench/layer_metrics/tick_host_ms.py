"""Mean host time per scheduler tick in its ingest, admit and gather
phases (the program's own tick-phase spans)."""


def read(r):
    spans = r.get("program_spans") or []
    ticks = [d for n, _, d in spans if n == "tick"]
    host = sum(d for n, _, d in spans if n in ("ingest", "admit", "gather"))
    return 1e-6 * host / len(ticks) if ticks else None
