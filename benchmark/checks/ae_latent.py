"""What a pass of the ``ae_latent`` mix left against a plain reference of the
upstream's autoencoder (Anovos v1.1.0 ``autoencoder_latentFeatures``,
transformers.py:2524-2892) on the same parquet files.  The reference is
written out here in numpy float64 and plain ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``: no kernel, no ``optax``, nothing
of ``anovos_tpu``.

The model: n - 2n - n - k - n - 2n - n; on the four hidden blocks Dense,
BatchNorm (momentum 0.99, eps 1e-3; the batch's biased variance in training,
the running statistics in inference), LeakyReLU(0.3); the bottleneck and the
output are Dense alone; MSE; Adam (1e-3, 0.9, 0.999, 1e-8, bias-corrected).
The preparation: every feature's nulls filled with its median, then
``(x - mean) / stddev`` (sample, n - 1), from the file's values in float64;
(5) below.

Where the program departs from the upstream's Keras graph, the reference
follows the program, and says so: (1) no shuffle buffer: an epoch's batches
are the first ``steps x batch`` indices of one ``jax.random.permutation`` of
the fit rows, from a key split off ``PRNGKey(0)`` (``key, sub = split(key)``
an epoch), the tail dropped, where Keras reshuffles with numpy and keeps a
short last batch; (2) the 80 / 20 split is by position (the first 80 % of the
rows train, the rest validate), where the upstream splits a Spark sample at
random; (3) the initial weights are He-normal draws (``normal(k1) *
sqrt(2 / n_in)``, ``k1`` the first half of the layer's key of
``split(PRNGKey(0), 6)``), biases 0, where Keras' Dense default is Glorot
uniform; (4) the validation MSE is taken once an epoch over the whole
validation block; (5) a feature's mean and standard deviation are those of
its values present, taken before the fill (the same where nothing is null).

Three comparisons (tolerances in the configuration, ``guarantees``):

``latent``: every written ``latent_i`` of every row against the float64
forward pass through the **saved** weights on the reference's own
standardised block; reported as the worst ``|ours - reference|`` over
``scale_share x`` the reference column's standard deviation.
``history``: the reference trains the same epochs from the same initial
weights in the same batch order; training and validation MSE of every epoch,
by their worst relative gap; and the last validation MSE below the first
and below 1.0 (the variance of a standardised feature: the model learnt).
``counts``, exact: the ``ae/fit`` row's ``steps``, ``epochs``, ``batch``,
``fit_rows``, ``val_rows``, ``params`` against the arithmetic; the shapes in
``model.npz``; the output's columns and rows; the label row for row.

args: ``label``, ``model``.  Tables: final_dataset, history."""

import glob
import json
import os

import numpy as np
import pandas as pd

from benchmark.harness.check import exact

LAYERS = ("enc1", "enc2", "bottleneck", "dec1", "dec2", "out")
HIDDEN = ("enc1", "enc2", "dec1", "dec2")  # Dense + BatchNorm + LeakyReLU; the other two are Dense alone
BN_MOMENTUM, BN_EPS, LEAK = 0.99, 1e-3, 0.3
ADAM_LR, ADAM_B1, ADAM_B2, ADAM_EPS = 1e-3, 0.9, 0.999, 1e-8
SAMPLE_SIZE, VALIDATION_FROM = 500_000, 0.8  # the upstream's defaults
FIT_COUNTS = ("steps", "epochs", "batch", "fit_rows", "val_rows", "params")


def layer_dims(n: int, k: int) -> list:
    return [(n, 2 * n), (2 * n, n), (n, k), (k, n), (n, 2 * n), (2 * n, n)]


def fit_arithmetic(rows: int, n: int, k: int, epochs: int, batch: int) -> dict:
    n_fit = min(rows, SAMPLE_SIZE)
    split = int(n_fit * VALIDATION_FROM)
    batch = min(batch, max(split, 1))
    weights = sum(i * o for i, o in layer_dims(n, k))
    trainable = weights + sum(3 * o if name in HIDDEN else o for name, (_, o) in zip(LAYERS, layer_dims(n, k)))
    return {"steps": epochs * max(split // batch, 1), "epochs": epochs, "batch": batch, "fit_rows": split,
            "val_rows": n_fit - split, "params": trainable}


# ------------------------------------------------------------ what a pass left ----
def read(out_dir, traffic, args):
    parts = sorted(glob.glob(os.path.join(out_dir, traffic["tables"]["final_dataset"])))
    df = pd.concat([pd.read_parquet(f) for f in parts], ignore_index=True)
    with np.load(os.path.join(out_dir, args["model"])) as blob:
        weights = {key: blob[key] for key in blob.files}
    with open(os.path.join(out_dir, traffic["manifest"])) as f:
        phases = json.load(f).get("phases") or []
    fit = [r["counts"] for r in phases if r["name"] == "ae/fit"]
    latents = [c for c in df.columns if c.startswith("latent_")]
    return {"rows": len(df), "names": dict(enumerate(df.columns)), "label": df[args["label"]],
            "latent": df[latents].to_numpy(np.float64),
            "weights": weights,
            "shapes": {key: "x".join(map(str, w.shape)) for key, w in weights.items() if key.endswith(".w")},
            "history": pd.read_csv(glob.glob(os.path.join(out_dir, traffic["tables"]["history"]))[0]),
            "fit": {key: fit[0].get(key) for key in FIT_COUNTS} if len(fit) == 1 else {}}


# ------------------------------------------------------------ the reference ----
def standardised(features: pd.DataFrame) -> np.ndarray:
    """Median fill and z-scores in float64 from the file's values.  As in the
    program, a feature's mean and standard deviation (sample, n - 1) are those
    of the values present, taken before the fill; the upstream standardises
    the filled column.  A table without nulls, as epsilon, reads the same."""
    x = features.to_numpy(np.float64)
    mean, std = np.nanmean(x, axis=0), np.nanstd(x, axis=0, ddof=1)
    if np.isnan(x).any():
        x = np.where(np.isnan(x), np.nanmedian(x, axis=0), x)
    return (x - mean) / np.where(std > 0, std, 1.0)


def forward64(weights: dict, block: np.ndarray, operands=None, rows_at_a_time: int = 8192) -> np.ndarray:
    """The encoder in inference mode, numpy float64, through saved weights
    (``model.npz``'s keys): the latent block.  ``operands``: a dtype each
    product's two operands are rounded to first (the control)."""
    w = {key: np.asarray(v, np.float64) for key, v in weights.items() if v.ndim}

    def lower(a):
        return a if operands is None else a.astype(np.float32).astype(operands).astype(np.float64)

    out = []
    for lo in range(0, len(block), rows_at_a_time):
        h = block[lo:lo + rows_at_a_time]
        for name in ("enc1", "enc2"):
            h = lower(h) @ lower(w[name + ".w"]) + w[name + ".b"]
            h = ((h - w[name + ".bn.mean"]) / np.sqrt(w[name + ".bn.var"] + BN_EPS)
                 * w[name + ".bn.scale"] + w[name + ".bn.bias"])
            h = np.where(h >= 0, h, LEAK * h)
        out.append(lower(h) @ lower(w["bottleneck.w"]) + w["bottleneck.b"])
    return np.concatenate(out)


def initial_state(n: int, k: int):
    """``(trainable, running)``: He-normal matrices from ``PRNGKey(0)``, zero
    biases, BatchNorm at scale 1, bias 0, mean 0, variance 1."""
    import jax
    import jax.numpy as jnp

    trainable, running = {}, {}
    for name, key, (i, o) in zip(LAYERS, jax.random.split(jax.random.PRNGKey(0), 6), layer_dims(n, k)):
        k1, _ = jax.random.split(key)
        trainable[name] = {"w": jax.random.normal(k1, (i, o), jnp.float32) * jnp.sqrt(2.0 / i),
                           "b": jnp.zeros((o,), jnp.float32)}
        if name in HIDDEN:
            trainable[name].update(scale=jnp.ones((o,), jnp.float32), bias=jnp.zeros((o,), jnp.float32))
            running[name] = {"mean": jnp.zeros((o,), jnp.float32), "var": jnp.ones((o,), jnp.float32)}
    return trainable, running


def forward(trainable, running, x, train: bool):
    """``(x_hat, running')``: the whole model, plain ``jax.numpy``."""
    import jax.numpy as jnp

    h, new_running = x, {}
    for name in LAYERS:
        p = trainable[name]
        h = h @ p["w"] + p["b"]
        if name not in HIDDEN:
            continue
        if train:
            mean, var = h.mean(axis=0), h.var(axis=0)
            new_running[name] = {"mean": BN_MOMENTUM * running[name]["mean"] + (1 - BN_MOMENTUM) * mean,
                                 "var": BN_MOMENTUM * running[name]["var"] + (1 - BN_MOMENTUM) * var}
        else:
            mean, var = running[name]["mean"], running[name]["var"]
        h = (h - mean) / jnp.sqrt(var + BN_EPS) * p["scale"] + p["bias"]
        h = jnp.where(h >= 0, h, LEAK * h)
    return h, (new_running if train else running)


def loss_and_running(trainable, running, batch):
    import jax.numpy as jnp

    x_hat, new_running = forward(trainable, running, batch, True)
    return jnp.mean((x_hat - batch) ** 2), new_running


def adam(trainable, grads, m, v, t, bias_correction: bool = True):
    """One update of Adam written out; ``t`` counts from 1."""
    import jax
    import jax.numpy as jnp

    m = jax.tree.map(lambda a, g: ADAM_B1 * a + (1 - ADAM_B1) * g, m, grads)
    v = jax.tree.map(lambda a, g: ADAM_B2 * a + (1 - ADAM_B2) * g * g, v, grads)
    c1, c2 = (1 - ADAM_B1 ** t, 1 - ADAM_B2 ** t) if bias_correction else (1.0, 1.0)
    trainable = jax.tree.map(lambda p, a, b: p - ADAM_LR * (a / c1) / (jnp.sqrt(b / c2) + ADAM_EPS), trainable, m, v)
    return trainable, m, v


def train_step(state, batch, running_in_training: bool = False, bias_correction: bool = True):
    """``state``: (trainable, running, m, v, t).  Returns the new state and
    the batch's loss before the update.  The two switches are the faults the
    tests show the band to catch; a reference run leaves them alone."""
    import jax

    trainable, running, m, v, t = state
    def loss_fn(tr):
        loss, new_running = loss_and_running(tr, running, batch)
        if running_in_training:  # the fault: the loss through the running statistics
            loss = ((forward(tr, running, batch, False)[0] - batch) ** 2).mean()
        return loss, new_running

    (loss, running), grads = jax.value_and_grad(loss_fn, has_aux=True)(trainable)
    trainable, m, v = adam(trainable, grads, m, v, t + 1.0, bias_correction)
    return (trainable, running, m, v, t + 1.0), loss


def train(block: np.ndarray, n: int, k: int, epochs: int, batch: int, **faults):
    """The reference's fit on a standardised block: the history (one row an
    epoch: mean training MSE over its steps, validation MSE at its end) and
    the final ``(trainable, running)``."""
    import jax
    import jax.numpy as jnp

    counts = fit_arithmetic(len(block), n, k, epochs, batch)
    split, batch = counts["fit_rows"], counts["batch"]
    steps = counts["steps"] // epochs
    with jax.default_matmul_precision("highest"):
        x_fit = jnp.asarray(block[:split], jnp.float32)
        x_val = jnp.asarray(block[split:split + counts["val_rows"]], jnp.float32)
        trainable, running = initial_state(n, k)
        zeros = jax.tree.map(jnp.zeros_like, trainable)
        state = (trainable, running, zeros, zeros, jnp.zeros((), jnp.float32))

        @jax.jit
        def epoch(state, epoch_key, x_fit):
            perm = jax.random.permutation(epoch_key, x_fit.shape[0])

            def body(s, carry):
                state, total = carry
                idx = jax.lax.dynamic_slice_in_dim(perm, s * batch, batch)
                state, loss = train_step(state, x_fit[idx], **faults)
                return state, total + loss

            state, total = jax.lax.fori_loop(0, steps, body, (state, jnp.zeros((), jnp.float32)))
            return state, total / steps

        @jax.jit
        def validation(state, x_val):
            x_hat, _ = forward(state[0], state[1], x_val, False)
            return jnp.mean((x_hat - x_val) ** 2)

        key, rows = jax.random.PRNGKey(0), []
        for _ in range(epochs):
            key, sub = jax.random.split(key)
            state, loss = epoch(state, sub, x_fit)
            rows.append((loss, validation(state, x_val) if len(x_val) else jnp.nan))
        rows = jax.device_get(rows)
    history = pd.DataFrame({"epoch": np.arange(epochs), "loss": [float(r[0]) for r in rows],
                            "val_loss": [float(r[1]) for r in rows]})
    return history, state[0], state[1]


def reference(frames, args):
    main = frames.main
    cfg = frames.pipeline["transformers"]["numerical_latentFeatures"]["autoencoder_latentFeatures"]
    features = main.drop(columns=[args["label"]])
    n = features.shape[1]
    r = cfg.get("reduction_params", 0.5)
    k = max(1, min(int(round(r * n)) if r < 1 else int(r), n))
    epochs, batch = int(cfg.get("epochs", 100)), int(cfg.get("batch_size", 256))
    block = standardised(features)
    history, _, _ = train(block, n, k, epochs, batch)
    names = [args["label"]] + [f"latent_{i}" for i in range(k)]
    dims = layer_dims(n, k)
    return {"rows": len(main), "names": dict(enumerate(names)), "label": main[args["label"]],
            "block": block, "history": history, "fit": fit_arithmetic(len(main), n, k, epochs, batch),
            "shapes": {f"{name}.w": f"{i}x{o}" for name, (i, o) in zip(LAYERS, dims)}}


def control(ans, ref):
    """The control: the answers as the same float64 reference gives them with
    both operands of each product rounded to an 8-bit float; not correct."""
    import ml_dtypes

    return dict(ans, latent=forward64(ans["weights"], ref["block"], operands=ml_dtypes.float8_e4m3fn))


# ------------------------------------------------------------ the comparison ----
def _latent_row(got: np.ndarray, want: np.ndarray, tol: dict) -> dict:
    if got.shape != want.shape:
        return {"name": "latent", "value": float("inf"), "limit": 1.0, "ok": False,
                "detail": f"shape {got.shape} vs {want.shape}"}
    scale = want.std(axis=0)
    ratio = np.abs(got - want) / (tol["scale_share"] * scale)
    ratio = np.where(np.isnan(ratio), np.inf, ratio)
    i, j = np.unravel_index(int(np.argmax(ratio)), ratio.shape)
    return {"name": "latent", "value": float(ratio[i, j]), "limit": 1.0, "ok": bool(ratio[i, j] <= 1.0),
            "detail": f"worst row {i} latent_{j}: {got[i, j]:.6g} vs {want[i, j]:.6g} (column std {scale[j]:.4g}; "
                      f"median gap {np.median(np.abs(got - want) / scale):.2e} of a column's std)"}


def _history_rows(got: pd.DataFrame, want: pd.DataFrame, tol: dict) -> list:
    g = got.set_index("epoch").reindex(want["epoch"])
    rows = []
    for col in ("loss", "val_loss"):
        gap = np.abs(g[col].to_numpy(float) - want[col].to_numpy(float)) / np.abs(want[col].to_numpy(float))
        gap = np.where(np.isnan(gap), np.inf, gap)  # an epoch the pass did not write fails
        e = int(np.argmax(gap))
        rows.append({"name": f"history_{col}", "value": float(gap[e] / tol["rtol"]), "limit": 1.0,
                     "ok": bool(gap[e] <= tol["rtol"]) and len(got) == len(want),
                     "detail": f"worst epoch {e}: {g[col].iloc[e]:.6g} vs {want[col].iloc[e]:.6g} (rel {gap[e]:.2e}); "
                               f"{len(got)} epochs written"})
    first, last = float(got["val_loss"].iloc[0]), float(got["val_loss"].iloc[-1])
    rows.append({"name": "val_loss_last", "value": last, "limit": min(first, 1.0), "ok": last < min(first, 1.0),
                 "detail": f"first epoch {first:.6g}"})
    return rows


def compare(ans, ref, tolerances, args):
    same_rows = len(ans["label"]) == len(ref["label"])
    changed = int((ans["label"].to_numpy() != ref["label"].to_numpy()).sum()) if same_rows else len(ref["label"])
    return [exact("rows", ans["rows"], ref["rows"]),
            exact("column_names", ans["names"], ref["names"]),
            exact("label_rows_changed", changed, 0),
            exact("fit_counts", ans["fit"], ref["fit"]),
            exact("model_shapes", ans["shapes"], ref["shapes"]),
            _latent_row(ans["latent"], forward64(ans["weights"], ref["block"]), tolerances["latent"]),
            *_history_rows(ans["history"], ref["history"], tolerances["history"])]
