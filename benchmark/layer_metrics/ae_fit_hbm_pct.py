"""The share of the chip's memory bandwidth that the autoencoder's training
steps reach in the traced pass: ``steps x`` the bytes no implementation of a
step can avoid, over the device seconds under ``ae/train_step``
(``ae_fit_device_s``) x the peak (``harness/peaks.json``,
``hbm_bytes_per_s``).  The bytes a step: the f32 master copy of every
trainable parameter and Adam's two moments of it, each read once and written
once (24 bytes a parameter), and the batch read once (``batch x n`` f32).
Gradients written and read back, bf16 copies of the weights and the
activations are an implementation's own and are not counted, so the share
reads the same work whatever implements the step and cannot pass 100.  The
shape comes from the traced pass's stage rows, as ``ae_fit_mfu_pct`` reads
it.  Nothing without a trace, the rows or the scope."""

from benchmark.harness import phases
from benchmark.harness.names import load_module


MFU = load_module("layer_metrics", "ae_fit_mfu_pct")  # the shape's reading and the counts, kept in one place


def step_bytes(n: int, k: int, batch: int) -> int:
    return 24 * MFU.trainable(n, k) + 4 * batch * n


def read(run):
    seconds = load_module("layer_metrics", "ae_fit_device_s").by_scope(run).get("ae/train_step")
    shape = MFU.fit_shape(phases.rows(run.get("traced")))
    top = MFU.peak("hbm_bytes_per_s") if seconds and shape else None
    if top is None:
        return None
    return MFU.share_pct(shape["steps"] * step_bytes(shape["n"], shape["k"], shape["batch"]), seconds, top)
