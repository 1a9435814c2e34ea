"""Roofline share (%) of the Gen-DST search: the time its work
(``work.gen_dst_work`` from the cell's shapes) needs at the chip's peaks,
over the device time of the Gen-DST programs (``jit__gen_dst_core``) that
lie wholly inside the trace.  Nothing traced, nothing reported."""
import trace_reduce
import work

PROGRAM = "jit__gen_dst_core"


def read(rec):
    if rec.trace is None:
        return None
    secs, count = trace_reduce.program_seconds(rec.trace, PROGRAM)
    if count < 1 or secs <= 0:
        return None
    cfg = rec.config
    t = cfg["table"]
    n_train = t["n_rows"] - max(1, int(t["test_frac"] * t["n_rows"]))
    ops, nbytes = work.gen_dst_work(
        N=n_train, M=t["n_cols"] + 1, B=256, n=cfg["plan"]["dst_rows"],
        **cfg["plan"]["gen_dst"])
    share, _bound = work.roofline_share(ops * count, nbytes * count, secs,
                                        work.peaks_for(rec.device["kind"]))
    return share
