"""Arena appends per scheduler tick: the program records one
``submit.append`` span for each dynamic_update_slice into the arena (the
writes SchedulerStats.arena_appends counts), over its ``tick`` spans."""


def read(r):
    names = [n for n, _, _ in r.get("program_spans") or []]
    ticks, appends = names.count("tick"), names.count("submit.append")
    return appends / ticks if ticks and appends else None
