"""Execute: FLOPs of the real (unpadded) rows of every expert flush in
the traced window, over the device time of the operations that ran
inside those flushes, over the bf16 peak (%).  Each expert's FLOPs
are its architecture's ``request_flops``."""


def read(run):
    tr = run.trace
    if tr is None:
        return None
    cfg, archs = run.cell.cfg, run.cell.archs
    work = 0
    for s in tr.spans_named("bench.flush"):
        i = s.stat("expert")
        work += archs[i].request_flops(cfg["experts"][i],
                                       cfg["seq_len"]) * s.stat("rows")
    busy = tr.device_s(tr.ops_in("bench.flush"))
    if not work or not busy:
        return None
    return 100.0 * work / busy / run.peaks["bf16_flops"]
