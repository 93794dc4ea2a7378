"""Mean duration of the scheduler's commit phase (the wait for the device,
the bits' transfer and their distribution to streams), from the program's
own tick-phase spans."""


def read(r):
    spans = r.get("program_spans") or []
    commits = [d for n, _, d in spans if n == "commit"]
    return 1e-6 * sum(commits) / len(commits) if commits else None
