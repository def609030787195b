"""The plain reference of the autoencoder for the tier-1 tests: a copy of the
reference half of ``benchmark/checks/ae_latent.py`` (the benchmark keeps its
own, so that neither side of a comparison can move the other).  numpy float64
and plain ``jax.numpy`` float32 under ``jax.default_matmul_precision("highest")``;
it imports nothing of ``anovos_tpu`` and no ``optax``.

The model: n - 2n - n - k - n - 2n - n; on the four hidden blocks Dense,
BatchNorm (momentum 0.99, eps 1e-3; the batch's biased variance in training,
the running statistics in inference), LeakyReLU(0.3); the bottleneck and the
output are Dense alone; MSE; Adam (1e-3, 0.9, 0.999, 1e-8, bias-corrected).
Where the program departs from the upstream's Keras graph the reference
follows the program: no shuffle buffer (an epoch's batches are the first
``steps x batch`` indices of one ``jax.random.permutation`` from a key split
off ``PRNGKey(0)``, the tail dropped), the 80 / 20 split by position,
He-normal initial weights from ``PRNGKey(0)``."""

import numpy as np
import pandas as pd

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
