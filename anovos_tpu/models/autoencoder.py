"""Symmetric autoencoder for latent features — the flagship model.

Architecture mirrors the reference's Keras AE (transformers.py:2793-2819):
n → 2n → n → bottleneck → n → 2n → n, BatchNorm + LeakyReLU on every hidden
layer, linear output, Adam on MSE.  Implementation is pure JAX + optax with
an explicit parameter pytree so the layout can be sharded over a
(data, model) mesh:

- batch axis rides ``data`` (DP) — gradients psum over ICI automatically;
- the two widest layers (n→2n and 2n→n) are column/row-sharded over
  ``model`` (Megatron-style pair: the 2n activation dimension is sharded,
  the following row-sharded matmul contracts it back with one psum) — the
  tensor-parallel analogue SURVEY.md §2.10 asks the design to keep open.

Training is a jitted ``lax.scan``-free minibatch loop (one gather per epoch,
one dispatch per step: the batch is sliced inside the step's program; donated
model and optimizer state) — the whole dataset stays device-resident, and the losses
stay on the device until the fit is over (``AutoEncoder.history``, saved as
``history.csv`` beside ``model.npz``).

Mixed precision: on TPU the dense matmuls run with bfloat16 inputs and
float32 accumulation (``preferred_element_type``) — the MXU's native mode —
while master weights, optimizer state, batch-norm statistics and the loss
stay float32.  This is the standard recipe for dense nets and is safe here
(the on-hardware sweep that showed bf16 corrupting *distance/covariance*
expansions — commit e7e831c — does not apply: those are quadratic
cancellation-prone forms; an AE layer is a plain affine map).  Control it
with ``compute_dtype=`` ("bf16" | "f32" | "auto") or ``ANOVOS_AE_COMPUTE``.
"""

from __future__ import annotations

import functools
import logging
import os
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pandas as pd
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from anovos_tpu.shared.runtime import DATA_AXIS, MODEL_AXIS

logger = logging.getLogger(__name__)

MODEL_DIR = "autoencoders_latentFeatures"
HISTORY_FILE = "history.csv"  # the fit's History, beside model.npz


def _dense_init(key, n_in, n_out, dtype=jnp.float32):
    k1, _ = jax.random.split(key)
    scale = jnp.sqrt(2.0 / n_in)
    return {
        "w": jax.random.normal(k1, (n_in, n_out), dtype) * scale,
        "b": jnp.zeros((n_out,), dtype),
    }


def _bn_init(n, dtype=jnp.float32):
    return {
        "scale": jnp.ones((n,), dtype),
        "bias": jnp.zeros((n,), dtype),
        "mean": jnp.zeros((n,), dtype),
        "var": jnp.ones((n,), dtype),
    }


_LAYERS = ("enc1", "enc2", "bottleneck", "dec1", "dec2", "out")


def _resolve_compute_dtype(requested: str):
    """Precedence: explicit constructor arg > ANOVOS_AE_COMPUTE env > auto
    (bf16 on TPU — the MXU's native mode — f32 elsewhere)."""
    req = (requested or "auto").lower()
    if req == "auto":
        req = os.environ.get("ANOVOS_AE_COMPUTE", "auto").lower()
    if req == "auto":
        req = "bf16" if jax.default_backend() == "tpu" else "f32"
    return jnp.bfloat16 if req in ("bf16", "bfloat16") else None


def _dense(x, layer, compute_dtype):
    """x @ w + b with optional bf16 inputs / f32 accumulation.

    ``preferred_element_type=float32`` keeps the MXU accumulating in f32 and
    propagates through the dot's transpose rule, so gradients accumulate in
    f32 too; the bias add and everything downstream stay f32.
    """
    w = layer["w"]
    if compute_dtype is not None:
        y = jnp.matmul(
            x.astype(compute_dtype),
            w.astype(compute_dtype),
            preferred_element_type=jnp.float32,
        )
    else:
        y = x @ w
    return y + layer["b"]


def _block(x, layer, train: bool, compute_dtype, momentum: float = 0.99):
    """Dense → BatchNorm → LeakyReLU; returns (y, updated_bn)."""
    h = _dense(x, layer, compute_dtype)
    bn = layer["bn"]
    if train:
        mu = h.mean(axis=0)
        var = h.var(axis=0)
        new_bn = {
            "scale": bn["scale"],
            "bias": bn["bias"],
            "mean": momentum * bn["mean"] + (1 - momentum) * mu,
            "var": momentum * bn["var"] + (1 - momentum) * var,
        }
    else:
        mu, var = bn["mean"], bn["var"]
        new_bn = bn
    hn = (h - mu) / jnp.sqrt(var + 1e-3) * bn["scale"] + bn["bias"]
    return jax.nn.leaky_relu(hn, 0.3), new_bn


def _encode(params: Dict, x: jax.Array, train: bool, compute_dtype):
    new_params = dict(params)
    h, bn = _block(x, params["enc1"], train, compute_dtype)
    new_params["enc1"] = {**params["enc1"], "bn": bn}
    h, bn = _block(h, params["enc2"], train, compute_dtype)
    new_params["enc2"] = {**params["enc2"], "bn": bn}
    z = _dense(h, params["bottleneck"], compute_dtype)
    return z, new_params


def _forward(params: Dict, x: jax.Array, train: bool, compute_dtype):
    z, new_params = _encode(params, x, train, compute_dtype)
    h, bn = _block(z, params["dec1"], train, compute_dtype)
    new_params["dec1"] = {**params["dec1"], "bn": bn}
    h, bn = _block(h, params["dec2"], train, compute_dtype)
    new_params["dec2"] = {**params["dec2"], "bn": bn}
    x_hat = _dense(h, params["out"], compute_dtype)
    return x_hat, new_params


def _train_step(params, opt_state, batch, optimizer, compute_dtype):
    """Forward in training mode, backward, Adam: ``(params, opt_state, loss)``,
    the loss taken before the update.  Its device operations carry the scope
    ``ae/train_step``."""

    def loss_fn(params, batch):
        x_hat, new_params = _forward(params, batch, True, compute_dtype)
        return jnp.mean((x_hat - batch) ** 2), new_params

    with jax.named_scope("ae/train_step"):
        (loss, new_params), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(new_params, updates), opt_state, loss


# The fit's programs are functions of this module, jitted once: a jit made
# inside ``fit`` would be a new function, and a compile, in every pass.
@functools.partial(jax.jit, static_argnames=("learning_rate",))
def _adam_init(params, *, learning_rate: float):
    return optax.adam(learning_rate).init(params)


@functools.partial(jax.jit, static_argnames=("rows",))
def _epoch_rows(X, perm, *, rows: int):
    """The rows an epoch trains on, in its order: ``X[perm[:rows]]``.  Gathered
    once an epoch; a gather of the batch inside the step made the chip copy
    the whole of ``X`` (a change of layout) in every step, 1.0 ms of 2.9."""
    return X[perm[:rows]]


def _indexed_step(params, opt_state, loss_sum, epoch_X, s, *, batch_size, learning_rate, compute_dtype):
    """Step ``s`` of an epoch: rows ``[s * batch_size:][:batch_size]`` of the
    epoch's rows through :func:`_train_step`; the loss is added to ``loss_sum``."""
    batch = jax.lax.dynamic_slice_in_dim(epoch_X, s * batch_size, batch_size)
    params, opt_state, loss = _train_step(params, opt_state, batch, optax.adam(learning_rate), compute_dtype)
    return params, opt_state, loss_sum + loss


_STEP_STATICS = ("batch_size", "learning_rate", "compute_dtype")
# by "donate": the model, Adam's moments and the loss are updated in place on
# an accelerator; the CPU ignores donation and warns about it
_INDEXED_STEP = {False: jax.jit(_indexed_step, static_argnames=_STEP_STATICS),
                 True: jax.jit(_indexed_step, static_argnames=_STEP_STATICS, donate_argnums=(0, 1, 2))}


@functools.partial(jax.jit, static_argnames=("compute_dtype",))
def _validation_loss(params, V, *, compute_dtype):
    x_hat, _ = _forward(params, V, False, compute_dtype)
    return jnp.mean((x_hat - V) ** 2)


@functools.partial(jax.jit, static_argnames=("compute_dtype",))
def _latent_columns(params, x, *, compute_dtype):
    with jax.named_scope("ae/encode"):
        z, _ = _encode(params, x, False, compute_dtype)
        z = z.astype(jnp.float32)
        return tuple(z[:, i] for i in range(z.shape[1]))


class AutoEncoder:
    """n → 2n → n → k → n → 2n → n symmetric AE."""

    def __init__(
        self,
        n_inputs: int,
        n_bottleneck: int,
        seed: int = 0,
        compute_dtype: str = "auto",
    ):
        self.n_inputs = int(n_inputs)
        self.n_bottleneck = int(n_bottleneck)
        self.seed = seed
        self._requested_dtype = compute_dtype
        self._compute_dtype_cache = ()
        # the fit's History: one row an epoch (epoch, loss, val_loss); None until fitted or loaded
        self.history: Optional[pd.DataFrame] = None

    @property
    def compute_dtype(self):
        """Resolved lazily so constructing an AE never forces backend init."""
        if self._compute_dtype_cache == ():
            self._compute_dtype_cache = _resolve_compute_dtype(self._requested_dtype)
            # 'auto' silently picks bf16 on TPU, so CPU and TPU runs of the
            # same config can differ in the last bits — make the choice
            # visible once per model so that drift is attributable
            logging.getLogger("anovos_tpu.autoencoder").info(
                "autoencoder compute dtype resolved to %s (requested=%r, backend=%s)",
                "bfloat16+f32-accum" if self._compute_dtype_cache is not None else "float32",
                self._requested_dtype, jax.default_backend(),
            )
        return self._compute_dtype_cache

    # -- parameters ------------------------------------------------------
    @property
    def layer_dims(self):
        n, k = self.n_inputs, self.n_bottleneck
        return [(n, 2 * n), (2 * n, n), (n, k), (k, n), (n, 2 * n), (2 * n, n)]

    @property
    def n_weights(self) -> int:
        """Entries of the six matrices."""
        return sum(i * o for i, o in self.layer_dims)

    @property
    def n_trainable(self) -> int:
        """Matrices, biases, and BatchNorm's scale and bias on the four hidden blocks."""
        return self.n_weights + sum(o if name in ("out", "bottleneck") else 3 * o
                                    for name, (_, o) in zip(_LAYERS, self.layer_dims))

    def init_params(self) -> Dict:
        keys = jax.random.split(jax.random.PRNGKey(self.seed), 6)
        dims = self.layer_dims
        params = {}
        for name, key, (i, o) in zip(_LAYERS, keys, dims):
            params[name] = _dense_init(key, i, o)
            # BatchNorm on hidden blocks only — the bottleneck and output are
            # plain linear, matching the reference graph (transformers.py:2798-2806)
            if name not in ("out", "bottleneck"):
                params[name]["bn"] = _bn_init(o)
        return params

    def param_shardings(self, mesh: Mesh) -> Dict:
        """Megatron-style placement for the widest pair of layers; everything
        else replicated.  Applied with jax.device_put / jit in_shardings."""

        def spec(name, leaf_path):
            if name in ("enc1", "dec2"):  # n→2n: shard the 2n output dim
                if leaf_path == "w":
                    return P(None, MODEL_AXIS)
                return P(MODEL_AXIS)  # bias + bn over the sharded dim
            if name in ("enc2", "out"):  # 2n→n: shard the 2n input dim
                if leaf_path == "w":
                    return P(MODEL_AXIS, None)
                return P()
            return P()

        shardings = {}
        for name in _LAYERS:
            layer = {
                "w": NamedSharding(mesh, spec(name, "w")),
                "b": NamedSharding(mesh, spec(name, "b") if name in ("enc1", "dec2") else P()),
            }
            if name not in ("out", "bottleneck"):
                bnspec = P(MODEL_AXIS) if name in ("enc1", "dec2") else P()
                layer["bn"] = {
                    k: NamedSharding(mesh, bnspec) for k in ("scale", "bias", "mean", "var")
                }
            shardings[name] = layer
        return shardings

    # -- forward ---------------------------------------------------------
    def encode(self, params: Dict, x: jax.Array, train: bool = False):
        """Returns (z, params_with_updated_bn)."""
        return _encode(params, x, train, self.compute_dtype)

    def forward(self, params: Dict, x: jax.Array, train: bool = False):
        """Full reconstruction; returns (x_hat, params_with_updated_bn)."""
        return _forward(params, x, train, self.compute_dtype)

    def reconstruct(self, params: Dict, x: jax.Array) -> jax.Array:
        x_hat, _ = self.forward(params, x, train=False)
        return x_hat

    def latent(self, params: Dict, x: jax.Array) -> jax.Array:
        z, _ = self.encode(params, x, train=False)
        return z

    def latent_columns(self, params: Dict, x: jax.Array) -> Tuple[jax.Array, ...]:
        """The encoder's output as one f32 array a latent feature, from one
        program (a table column is an array of its own: a slice a column
        would be a dispatch a column)."""
        return _latent_columns(params, x, compute_dtype=self.compute_dtype)

    # -- training --------------------------------------------------------
    def make_train_step(self, optimizer):
        # donate params + opt_state: XLA updates the weight/optimizer
        # buffers in place instead of allocating fresh ones every step —
        # halves the per-step HBM traffic and footprint for the model
        # state.  The fit loop rebinds both on every call, so the donated
        # (invalidated) inputs are never touched again.  CPU ignores
        # donation and warns about it, so only donate on accelerators.
        donate = () if jax.default_backend() == "cpu" else (0, 1)

        @functools.partial(jax.jit, donate_argnums=donate)
        def train_step(params, opt_state, batch):
            return _train_step(params, opt_state, batch, optimizer, self.compute_dtype)

        return train_step

    def fit(
        self,
        X: jax.Array,
        epochs: int = 100,
        batch_size: int = 256,
        learning_rate: float = 1e-3,
        validation_X: Optional[jax.Array] = None,
        verbose: bool = False,
        seed: int = 0,
    ) -> Dict:
        """Minibatch Adam training; X must be standardized & imputed.

        The batch order: one ``jax.random.permutation`` of the rows an epoch,
        from a key split off ``PRNGKey(seed)``; its first ``steps x
        batch_size`` indices in order, the tail dropped.  One gather an epoch
        (its rows in its order), one dispatch a step (the batch is a slice of
        them, taken inside the step's program), one wait an epoch (for
        its validation loss) and no fetch inside the loop unless
        ``verbose``: each epoch's summed training loss and its validation
        loss stay on the device and come to the host once, as
        ``self.history`` (what Keras' ``History`` holds upstream: per epoch
        the mean training MSE over its steps, each taken before its update,
        and the validation MSE at the epoch's end, in inference mode)."""
        params = self.init_params()
        opt_state = _adam_init(params, learning_rate=float(learning_rate))
        n = X.shape[0]
        steps_per_epoch = max(n // batch_size, 1)
        step = _INDEXED_STEP[jax.default_backend() != "cpu"]
        static = dict(batch_size=int(batch_size), learning_rate=float(learning_rate),
                      compute_dtype=self.compute_dtype)
        key = jax.random.PRNGKey(seed)
        train_sums, val_losses = [], []
        for ep in range(epochs):
            key, sub = jax.random.split(key)
            epoch_X = _epoch_rows(X, jax.random.permutation(sub, n), rows=min(steps_per_epoch * batch_size, n))
            loss_sum = jnp.zeros((), jnp.float32)
            for s in range(steps_per_epoch):
                params, opt_state, loss_sum = step(params, opt_state, loss_sum, epoch_X, np.int32(s), **static)
            train_sums.append(loss_sum)
            if validation_X is not None:
                # waited for, an epoch: the next epoch's first step does not depend on it, and two
                # independent programs with collectives in flight deadlock a multi-device mesh
                val_losses.append(jax.block_until_ready(
                    _validation_loss(params, validation_X, compute_dtype=self.compute_dtype)))
            if verbose and (ep % 10 == 0 or ep == epochs - 1):
                msg = f"epoch {ep}: train mse {float(loss_sum) / steps_per_epoch:.5f}"
                if validation_X is not None:
                    msg += f" val mse {float(val_losses[-1]):.5f}"
                logger.info(msg)
        train_sums, val_losses = jax.device_get((train_sums, val_losses))
        self.history = pd.DataFrame({
            "epoch": np.arange(epochs),
            "loss": np.asarray(train_sums, np.float32).reshape(-1) / np.float32(steps_per_epoch),
            "val_loss": (np.asarray(val_losses, np.float32).reshape(-1) if validation_X is not None
                         else np.full(epochs, np.nan, np.float32)),
        })
        return params

    # -- persistence -----------------------------------------------------
    def save(self, params: Dict, model_path: str) -> None:
        d = os.path.join(model_path, MODEL_DIR)
        os.makedirs(d, exist_ok=True)
        flat = {}
        for lname, layer in jax.device_get(params).items():  # one fetch of the tree, its copies in flight together
            for k, v in layer.items():
                if k == "bn":
                    for bk, bv in v.items():
                        flat[f"{lname}.bn.{bk}"] = np.asarray(bv)
                else:
                    flat[f"{lname}.{k}"] = np.asarray(v)
        np.savez(
            os.path.join(d, "model.npz"),
            n_inputs=self.n_inputs,
            n_bottleneck=self.n_bottleneck,
            **flat,
        )
        if self.history is not None:  # a model that was fitted here, not loaded and saved again
            self.history.to_csv(os.path.join(d, HISTORY_FILE), index=False)

    @staticmethod
    def load(model_path: str) -> Tuple["AutoEncoder", Dict]:
        blob = np.load(os.path.join(model_path, MODEL_DIR, "model.npz"))
        ae = AutoEncoder(int(blob["n_inputs"]), int(blob["n_bottleneck"]))
        history = os.path.join(model_path, MODEL_DIR, HISTORY_FILE)
        if os.path.exists(history):
            ae.history = pd.read_csv(history).astype({"loss": np.float32, "val_loss": np.float32})
        params: Dict = {}
        for key in blob.files:
            if key in ("n_inputs", "n_bottleneck"):
                continue
            parts = key.split(".")
            d = params
            for p in parts[:-1]:
                d = d.setdefault(p, {})
            d[parts[-1]] = jnp.asarray(blob[key])
        return ae, params
