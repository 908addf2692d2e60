"""Generated tokens (one per prefill, one per decode step of each live
lane) over the wall time of every chunk run in the window."""


def read(run):
    if run.kind != "serving" or run.work.get("chunk_s", 0) <= 0:
        return None
    return run.work["tokens"] / run.work["chunk_s"]
