"""``gather_blocks``'s share of its roofline (%): the least time the
window's paying cold block copies need at the chip's HBM bandwidth
(``bench/work.py``: io - dedup_saved, tier-0 hits excluded, each tile
read and written once), over the kernel's device time in the trace,
summed over devices. The kernel is bound by memory.

The kernel carries no name into the trace: it is the
``tpu_custom_call`` that takes the block ids (s32[R]) and the block
store (f32[rho, eps, D]) and returns f32[R, eps, D]."""
import work

KERNEL = (r'= f32\[\d+,\d+,\d+\]\S* custom-call\(s32\[\d+\]\S* %[\w.]+, '
          r'f32\[\d+,\d+,\d+\]\S* %[\w.\-]+\), '
          r'custom_call_target="tpu_custom_call"')


def read(run):
    t = run.trace
    if t is None or not t.devices or not run.batches:
        return None
    kernel_s = sum(t.op_ns(KERNEL)) / 1e9
    if kernel_s <= 0:
        return None
    sh = run.shapes
    need = work.gather_blocks_bytes(sum(b.paying_dmas for b in run.batches),
                                    sh["eps"], sh["dim"], sh["itemsize"])
    return 100.0 * need / run.peaks.hbm_bytes_per_s / kernel_s
