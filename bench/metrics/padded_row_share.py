"""Lanes: padded rows executed over all rows executed by expert flushes
up to the window's close (``EngineStats.padded_rows`` over the sum of
bucket size times ``bucket_hits``), in %."""


def read(run):
    rows = sum(int(b) * n for b, n in run.stats["bucket_hits"].items())
    if not rows:
        return None
    return 100.0 * run.stats["padded_rows"] / rows
