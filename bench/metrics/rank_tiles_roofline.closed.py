"""``rank_tiles``'s share of its roofline (%): the larger of the
window's operations over the chip's peak and its HBM bytes over the
chip's bandwidth (``bench/work.py``, one call per round per device at
the batch's rows), over the kernel's device time in the trace, summed
over devices. At these shapes the bytes bound it.

The kernel carries no name into the trace: it is the
``tpu_custom_call`` that returns (f32[Q, F*eps], s32[Q, n_expand])."""
import work

KERNEL = (r'= \(f32\[\d+,\d+\]\S*, s32\[\d+,\d+\]\S*\) custom-call\(.*'
          r'custom_call_target="tpu_custom_call"')


def read(run):
    t = run.trace
    if t is None or not t.devices or not run.batches:
        return None
    kernel_s = sum(t.op_ns(KERNEL)) / 1e9
    if kernel_s <= 0:
        return None
    sh, serve = run.shapes, run.config["serve"]
    expand = work.n_expand(serve["fetch_width"], sh["eps"], serve["sigma"])
    ops = byt = 0
    for b in run.batches:
        o, m = work.rank_tiles_work(b.rows, serve["fetch_width"], sh["eps"],
                                    sh["dim"], expand, sh["itemsize"])
        calls = sum(b.device_rounds)
        ops += o * calls
        byt += m * calls
    need_s = max(ops / run.peaks.bf16_flops,
                 byt / run.peaks.hbm_bytes_per_s)
    return 100.0 * need_s / kernel_s
