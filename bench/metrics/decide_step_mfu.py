"""Route as a whole step: router-encoder and decision-head FLOPs of the
real rows of every admission batch in the traced window, over the
device time of the operations that ran inside those admissions, over
the bf16 peak (%)."""

from bench import flops


def read(run):
    tr = run.trace
    if tr is None:
        return None
    cfg = run.cell.cfg
    r, S = cfg["router"], cfg["seq_len"]
    work = 0
    for s in tr.spans_named("bench.admit"):
        rows = s.stat("rows")
        work += rows * flops.router_request_flops(r, S)
        work += flops.decision_head_flops(
            rows, r["d_model"], r["head_hidden"], len(cfg["experts"]),
            len(cfg["constraints"]), run.cell.cascade)
    busy = tr.device_s(tr.ops_in("bench.admit"))
    if not work or not busy:
        return None
    return 100.0 * work / busy / run.peaks["bf16_flops"]
