"""Mean time per accepted chunk inside the program's own ``submit`` spans
(StreamScheduler.submit_chunk, entry to return), over the same chunks that
submit_host_ms divides by: what submit_host_ms reads beyond it is the
driver's own work around the calls."""


def read(r):
    chunks = r.get("chunks")
    submit = [d for n, _, d in r.get("program_spans") or [] if n == "submit"]
    return 1e-6 * sum(submit) / chunks if chunks and submit else None
