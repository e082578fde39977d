"""Share of the HBM-bandwidth roofline that the fleet's segmenter kernel
reaches on a chip: the bytes a segmentation of one chip's shard of the
pushed samples has to move (``bench/core/kernels.py``: 4 B read and 9 B
of events written per stream and step, the shard padded to 128 lanes)
over one chip's peak bandwidth, divided by the kernel's device time per
chip in the traced window (``Trace.op_s``, a mean over the chips)."""

from bench.core.kernels import is_segmenter, segmenter_bytes
from bench.core.peaks import peak


def read(run):
    rec = run.records
    pushes = rec.get("pushes")
    if run.trace is None or not pushes:
        return None
    kernel_s = run.trace.op_s(is_segmenter(run.cell.traffic["method"]))
    if kernel_s <= 0:
        return None
    moved = len(pushes) * segmenter_bytes(rec["n_streams"], rec["push_width"],
                                          run.cell.chips)
    least_s = moved / peak(run.device_kind, "hbm_bytes_per_s")
    return 100.0 * least_s / kernel_s
