"""Mean host time per accepted chunk of the streams' top-ups before each
tick: the submit_chunk calls (a host-to-device copy and an arena append
each), read by the benchmark's own clock."""


def read(r):
    chunks = r.get("chunks")
    return 1e3 * r["submit_s"] / chunks if chunks else None
