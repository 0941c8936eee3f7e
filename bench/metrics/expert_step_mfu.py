"""Execute: FLOPs of the real (unpadded) rows of every expert flush in
the traced window, over the device time of the operations that ran
inside those flushes, over the bf16 peak (%)."""

from bench import flops


def read(run):
    tr = run.trace
    if tr is None:
        return None
    cfg = run.cell.cfg
    work = sum(flops.expert_request_flops(cfg["experts"][s.stat("expert")],
                                          cfg["seq_len"]) * s.stat("rows")
               for s in tr.spans_named("bench.flush"))
    busy = tr.device_s(tr.ops_in("bench.flush"))
    if not work or not busy:
        return None
    return 100.0 * work / busy / run.peaks["bf16_flops"]
