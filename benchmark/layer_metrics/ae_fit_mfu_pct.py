"""The share of the chip's bf16 peak that the autoencoder's training steps
reach in the traced pass: ``steps x flops_per_step`` over the device seconds
under ``ae/train_step`` (``ae_fit_device_s``) x the peak
(``harness/peaks.json``, ``bf16_flops_per_s``).  The operations are the
model's, from its shape and not from the program's count: the six matrices of
n - 2n - n - k - n - 2n - n hold ``weights(n, k)`` entries, a multiply-add is
two operations, and a step is three products a matrix (forward, the
gradient of the input, the gradient of the weights): ``6 x batch x
weights``.  BatchNorm, the activations and Adam are not counted, so no
implementation can push the share above 100.  The shape comes from the
traced pass's stage rows (``ae/prep``: ``cols`` = n; ``ae/apply``:
``latent`` = k; ``ae/fit``: ``steps``, ``batch``); the program's own
``flops_per_step`` is only checked against this one.  At batch 256 a step
moves 24 bytes a parameter for 1,536 operations a parameter, so the step is
bound by memory traffic: 15-20 % here is a sound step (``ae_fit_hbm_pct``).
Nothing without a trace, the rows or the scope, or where the two counts
disagree."""

import json
import os

from benchmark.harness import phases
from benchmark.harness.names import BENCH, load_module


def weights(n: int, k: int) -> int:
    """Entries of the six matrices."""
    return n * 2 * n + 2 * n * n + n * k + k * n + n * 2 * n + 2 * n * n


def trainable(n: int, k: int) -> int:
    """Matrices, biases, BatchNorm's scale and bias on the four hidden blocks."""
    return weights(n, k) + 3 * (2 * n + n + n + 2 * n) + k + n


def flops_per_step(n: int, k: int, batch: int) -> int:
    return 6 * batch * weights(n, k)


def fit_shape(rows: list):
    """``{n, k, steps, batch, flops_per_step}`` from a pass's stage rows, or None."""
    by = {name: [r["counts"] for r in rows if r["name"] == name] for name in ("ae/prep", "ae/fit", "ae/apply")}
    if any(len(found) != 1 for found in by.values()):
        return None
    fit = by["ae/fit"][0]
    return {"n": by["ae/prep"][0]["cols"], "k": by["ae/apply"][0]["latent"], "steps": fit["steps"],
            "batch": fit["batch"], "flops_per_step": fit["flops_per_step"]}


def peak(key: str):
    """The attached device's peak of that name, or None for a device the table does not know."""
    import jax

    with open(os.path.join(BENCH, "harness", "peaks.json")) as f:
        return (json.load(f)["devices"].get(jax.devices()[0].device_kind) or {}).get(key)


def share_pct(amount: float, seconds: float, per_second: float) -> float:
    return 100.0 * amount / (seconds * per_second)


def read(run):
    seconds = load_module("layer_metrics", "ae_fit_device_s").by_scope(run).get("ae/train_step")
    shape = fit_shape(phases.rows(run.get("traced")))
    if not seconds or not shape:
        return None
    flops = flops_per_step(shape["n"], shape["k"], shape["batch"])
    top = peak("bf16_flops_per_s")
    if flops != shape["flops_per_step"] or top is None:
        return None
    return share_pct(shape["steps"] * flops, seconds, top)
