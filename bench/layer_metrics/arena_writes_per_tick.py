"""Device writes into the stream arena per scheduler tick: the program
records one ``arena.write`` span for each write of the rows staged since
the last one (the writes SchedulerStats.arena_writes counts), over its
``tick`` spans.  A program that writes on every append records no such
span, and reads nothing."""


def read(r):
    names = [n for n, _, _ in r.get("program_spans") or []]
    ticks, writes = names.count("tick"), names.count("arena.write")
    return writes / ticks if ticks and writes else None
