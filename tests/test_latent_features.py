"""Autoencoder + PCA latent feature tests."""

import numpy as np
import pandas as pd
import pytest

from anovos_tpu.data_transformer.latent_features import (
    PCA_latentFeatures,
    autoencoder_latentFeatures,
)
from anovos_tpu.models.autoencoder import AutoEncoder
from anovos_tpu.shared.table import Table
import jax.numpy as jnp


@pytest.fixture(scope="module")
def latent_df():
    """4 observed columns driven by 2 latent factors."""
    g = np.random.default_rng(21)
    n = 2000
    f1, f2 = g.normal(size=n), g.normal(size=n)
    return pd.DataFrame(
        {
            "a": f1 + 0.05 * g.normal(size=n),
            "b": -f1 + 0.05 * g.normal(size=n),
            "c": f2 + 0.05 * g.normal(size=n),
            "d": f2 + f1 + 0.05 * g.normal(size=n),
        }
    )


def test_autoencoder_trains_and_reconstructs(latent_df):
    t = Table.from_pandas(latent_df)
    ae = AutoEncoder(4, 2)
    from anovos_tpu.data_transformer.latent_features import _prep_block

    X, _, _ = _prep_block(t, ["a", "b", "c", "d"], True, True)
    Xr = X[: t.nrows]
    params = ae.fit(Xr, epochs=100, batch_size=256)  # reference default epochs
    mse = float(jnp.mean((ae.reconstruct(params, Xr) - Xr) ** 2))
    assert mse < 0.1  # 2 latent dims explain 4 correlated columns


def test_autoencoder_bf16_parity(latent_df):
    """The bf16-input / f32-accumulate matmul path (the TPU MXU recipe) must
    train to the same quality as pure f32 and reconstruct within bf16's
    representational tolerance (~8 mantissa bits → ~0.4% relative)."""
    t = Table.from_pandas(latent_df)
    from anovos_tpu.data_transformer.latent_features import _prep_block

    X, _, _ = _prep_block(t, ["a", "b", "c", "d"], True, True)
    Xr = X[: t.nrows]
    losses, recons = {}, {}
    for mode in ("f32", "bf16"):
        ae = AutoEncoder(4, 2, compute_dtype=mode)
        params = ae.fit(Xr, epochs=40, batch_size=256)
        recon = ae.reconstruct(params, Xr)
        losses[mode] = float(jnp.mean((recon - Xr) ** 2))
        recons[mode] = recon
    # both converge, and to comparable reconstruction quality
    assert losses["f32"] < 0.2 and losses["bf16"] < 0.2
    assert abs(losses["bf16"] - losses["f32"]) < 0.05
    # master weights stay f32 in both modes
    ae = AutoEncoder(4, 2, compute_dtype="bf16")
    p = ae.init_params()
    assert p["enc1"]["w"].dtype == jnp.float32
    # a single forward at identical params differs only by bf16 rounding
    xh_f32 = AutoEncoder(4, 2, compute_dtype="f32").reconstruct(p, Xr[:256])
    xh_bf16 = ae.reconstruct(p, Xr[:256])
    assert xh_bf16.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(xh_bf16), np.asarray(xh_f32), atol=0.1, rtol=0.05
    )


def test_autoencoder_latentFeatures_transformer(latent_df):
    t = Table.from_pandas(latent_df)
    out = autoencoder_latentFeatures(t, reduction_params=0.5, epochs=20, output_mode="replace")
    df = out.to_pandas()
    assert {"latent_0", "latent_1"} <= set(df.columns)
    assert "a" not in df.columns
    assert not df["latent_0"].isna().any()


def test_autoencoder_model_roundtrip(latent_df, tmp_path):
    t = Table.from_pandas(latent_df)
    mp = str(tmp_path / "ae")
    a = autoencoder_latentFeatures(t, epochs=5, model_path=mp, output_mode="append").to_pandas()
    b = autoencoder_latentFeatures(
        t, pre_existing_model=True, model_path=mp, output_mode="append"
    ).to_pandas()
    np.testing.assert_allclose(a["latent_0"].to_numpy(), b["latent_0"].to_numpy(), atol=1e-5)


def test_pca_latentFeatures(latent_df):
    t = Table.from_pandas(latent_df)
    out = PCA_latentFeatures(t, explained_variance_cutoff=0.95, output_mode="replace")
    df = out.to_pandas()
    latents = [c for c in df.columns if c.startswith("latent_")]
    # 2 factors dominate → ≤3 components reach 95%
    assert 2 <= len(latents) <= 3
    v = df[latents].var()
    assert v.iloc[0] >= v.iloc[-1]  # components ordered by variance


def test_pca_matches_float64_reference(latent_df):
    """PCA_latentFeatures against float64 numpy (centre, covariance over
    n-1, eigh, descending), compared free of each eigenvector's sign: the
    chosen k exactly, the kept spectrum relatively, |latent column|
    absolutely.  Read on this fixture: 1.5e-7 and 9.5e-7 (values up to 6.7,
    kept eigenvalues 3.51 and 1.32, the next 0.0025); held to 1e-3."""
    cutoff = 0.95
    out = PCA_latentFeatures(Table.from_pandas(latent_df), explained_variance_cutoff=cutoff)
    got = out.to_pandas()
    latents = [c for c in got.columns if c.startswith("latent_")]

    X = latent_df.to_numpy().astype(np.float32).astype(np.float64)
    Xc = X - X.mean(axis=0)
    w, v = np.linalg.eigh(Xc.T @ Xc / (len(X) - 1))
    order = np.argsort(w)[::-1]
    w, v = w[order], v[:, order]
    k = int(np.searchsorted(np.cumsum(w) / w.sum(), cutoff) + 1)

    assert latents == [f"latent_{i}" for i in range(k)]
    Z = got[latents].to_numpy().astype(np.float64)
    np.testing.assert_allclose(Z.var(axis=0, ddof=1), w[:k], rtol=1e-3)
    np.testing.assert_allclose(np.abs(Z), np.abs(Xc @ v[:, :k]), atol=1e-3)


def test_pca_model_roundtrip(latent_df, tmp_path):
    t = Table.from_pandas(latent_df)
    mp = str(tmp_path / "pca")
    a = PCA_latentFeatures(t, model_path=mp, output_mode="append").to_pandas()
    b = PCA_latentFeatures(t, pre_existing_model=True, model_path=mp, output_mode="append").to_pandas()
    np.testing.assert_allclose(a["latent_0"].to_numpy(), b["latent_0"].to_numpy(), atol=1e-4)


# ---------------------------------------------------------------------------
# the autoencoder against its plain reference (tests/ae_reference.py: numpy
# float64 and plain jax.numpy, no optax, nothing of anovos_tpu), on the CPU in
# f32 at a small size
# ---------------------------------------------------------------------------
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import jax  # noqa: E402
import optax  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import ae_reference as ref  # noqa: E402

N, K, BATCH = 12, 5, 32


def _to_reference(params):
    """The program's parameter tree as the reference's (trainable, running)."""
    trainable, running = {}, {}
    for name, layer in params.items():
        trainable[name] = {"w": layer["w"], "b": layer["b"]}
        if "bn" in layer:
            trainable[name].update(scale=layer["bn"]["scale"], bias=layer["bn"]["bias"])
            running[name] = {"mean": layer["bn"]["mean"], "var": layer["bn"]["var"]}
    return trainable, running


def _random_params(ae, seed):
    """Seeded random weights: every leaf moved off its initial value, BatchNorm's too."""
    params = ae.init_params()
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    moved = [leaf + 0.3 * jax.random.normal(k, leaf.shape, leaf.dtype) for k, leaf in zip(keys, leaves)]
    params = jax.tree.unflatten(tree, moved)
    for layer in params.values():  # a variance stays positive
        if "bn" in layer:
            layer["bn"]["var"] = jnp.abs(layer["bn"]["var"]) + 0.1
    return params


def _batches(count, seed=3):
    return [jax.random.normal(k, (BATCH, N), jnp.float32) * 1.5 + 0.2
            for k in jax.random.split(jax.random.PRNGKey(seed), count)]


@pytest.fixture(scope="module")
def small_ae():
    return AutoEncoder(N, K, compute_dtype="f32")


def test_the_reference_imports_neither_the_model_nor_optax():
    for path in (ref.__file__, os.path.join(os.path.dirname(ref.__file__), "..", "benchmark", "checks", "ae_latent.py")):
        with open(path) as f:
            code = f.read()
        assert "import optax" not in code and "from anovos_tpu" not in code and "import anovos_tpu" not in code
        assert 'default_matmul_precision("highest")' in code
    assert AutoEncoder(2000, 1000).n_weights == 36_000_000 == sum(i * o for i, o in ref.layer_dims(2000, 1000))
    assert AutoEncoder(2000, 1000).n_trainable == ref.fit_arithmetic(50_000, 2000, 1000, 10, 256)["params"] == 36_039_000
    assert ref.fit_arithmetic(50_000, 2000, 1000, 10, 256) == {
        "steps": 1560, "epochs": 10, "batch": 256, "fit_rows": 40_000, "val_rows": 10_000, "params": 36_039_000}


def test_one_step_has_the_references_loss_and_every_gradient_leaf(small_ae):
    params, batch = _random_params(small_ae, 1), _batches(1)[0]

    def loss_fn(p):
        x_hat, _ = small_ae.forward(p, batch, train=True)
        return jnp.mean((x_hat - batch) ** 2)

    loss, grads = jax.value_and_grad(loss_fn)(params)
    trainable, running = _to_reference(params)
    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.value_and_grad(lambda t: ref.loss_and_running(t, running, batch)[0])(trainable)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    got_grads, _ = _to_reference(grads)
    flat_got = jax.tree.leaves_with_path(got_grads)
    flat_want = dict(jax.tree.leaves_with_path(want_grads))
    assert len(flat_got) == len(flat_want) == 6 * 2 + 4 * 2
    for path, g in flat_got:
        w = np.asarray(flat_want[path])
        np.testing.assert_allclose(np.asarray(g), w, rtol=1e-5, atol=1e-5 * np.abs(w).max(), err_msg=str(path))
    # the running statistics carry no gradient: a batch's own statistics normalise it in training
    assert all(float(jnp.abs(grads[n]["bn"][s]).max()) == 0.0 for n in ref.HIDDEN for s in ("mean", "var"))


def test_five_adam_steps_leave_the_references_weights_and_running_statistics(small_ae):
    params, batches = _random_params(small_ae, 2), _batches(5)
    trainable, running = _to_reference(params)
    step = small_ae.make_train_step(optax.adam(1e-3))
    opt_state = optax.adam(1e-3).init(params)
    zeros = jax.tree.map(jnp.zeros_like, trainable)
    state = (trainable, running, zeros, zeros, jnp.zeros((), jnp.float32))
    for batch in batches:
        params, opt_state, loss = step(params, opt_state, batch)
        with jax.default_matmul_precision("highest"):
            state, want_loss = ref.train_step(state, batch)
        np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    got_trainable, got_running = _to_reference(params)
    compared = 0
    for got, want in ((got_trainable, state[0]), (got_running, state[1])):
        for (path, g), w in zip(jax.tree.leaves_with_path(got), jax.tree.leaves(want)):
            # a bias that a BatchNorm follows (the bottleneck's too: dec1's Dense is linear in it) is
            # taken out again with the batch's mean: its gradient is rounding noise around 0, and Adam
            # turns noise into steps of +-1e-3 that no two implementations share and no output sees
            if path[-1].key == "b" and path[0].key != "out":
                assert float(jnp.abs(g - w).max()) <= 5 * 2e-3
                continue
            # a running mean follows its batch means, which carry those biases: a hundredth of their steps
            atol = 5e-4 if path[-1].key == "mean" else 2e-6
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5, atol=atol, err_msg=str(path))
            compared += 1
    assert compared == 6 + 1 + 4 * 2 + 4 * 2  # matrices, the output's bias, BatchNorm's scale, bias, mean, variance
    # five steps moved every weight by about 5 x 1e-3, and the running statistics a twentieth of the way
    moved = np.abs(np.asarray(got_trainable["enc1"]["w"]) - np.asarray(trainable["enc1"]["w"]))
    assert 2e-3 < np.median(moved) < 6e-3
    assert float(jnp.abs(got_running["enc1"]["mean"] - running["enc1"]["mean"]).max()) > 1e-3
    # the faults the history's band has to catch are faults here too
    with jax.default_matmul_precision("highest"):
        for fault in ({"running_in_training": True}, {"bias_correction": False}):
            faulty, _ = ref.train_step((trainable, running, zeros, zeros, jnp.zeros((), jnp.float32)), batches[0], **fault)
            sound, _ = ref.train_step((trainable, running, zeros, zeros, jnp.zeros((), jnp.float32)), batches[0])
            assert float(jnp.abs(faulty[0]["enc1"]["w"] - sound[0]["enc1"]["w"]).max()) > 1e-4, fault


def test_the_encoders_output_is_the_float64_forward_pass_through_the_saved_weights(small_ae, tmp_path):
    params = _random_params(small_ae, 4)
    x = jnp.concatenate(_batches(3, seed=9))
    small_ae.save(params, str(tmp_path))
    with np.load(tmp_path / "autoencoders_latentFeatures" / "model.npz") as blob:
        saved = {k: blob[k] for k in blob.files}
    assert not (tmp_path / "autoencoders_latentFeatures" / "history.csv").exists()  # never fitted: no history
    want = ref.forward64(saved, np.asarray(x, np.float64))
    cols = small_ae.latent_columns(params, x)
    assert len(cols) == K and all(c.shape == (3 * BATCH,) and c.dtype == jnp.float32 for c in cols)
    np.testing.assert_allclose(np.stack([np.asarray(c) for c in cols], axis=1), want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(small_ae.latent(params, x)), want, rtol=2e-5, atol=2e-5)
    # rounding the operands of every product to 8 bits is seen, to bfloat16 far less
    import ml_dtypes

    low = ref.forward64(saved, np.asarray(x, np.float64), operands=ml_dtypes.float8_e4m3fn)
    mid = ref.forward64(saved, np.asarray(x, np.float64), operands=ml_dtypes.bfloat16)
    assert np.abs(low - want).mean() > 10 * np.abs(mid - want).mean() > 0


@pytest.fixture(scope="module")
def fitted(tmp_path_factory):
    """One fit through the transformer on 300 rows x 12 columns with a few nulls, in a pass of its own."""
    from anovos_tpu.obs import get_tracer

    g = np.random.default_rng(5)
    f = g.normal(size=(300, 3))
    x = f @ g.normal(size=(3, N)) + 0.1 * g.normal(size=(300, N))
    x[g.random(x.shape) < 0.02] = np.nan
    df = pd.DataFrame(x.astype(np.float32), columns=[f"c{i}" for i in range(N)])
    df.insert(0, "label", np.where(f[:, 0] > 0, 1, -1).astype(np.int32))
    model_path = str(tmp_path_factory.mktemp("ae_model"))
    tracer = get_tracer()
    with tracer.run_pass(), tracer.phase("dag"):
        out = autoencoder_latentFeatures(Table.from_pandas(df), drop_cols=["label"], reduction_params=0.5, epochs=4,
                                         batch_size=BATCH, model_path=model_path)
    return {"df": df, "out": out, "model_path": model_path, "phases": tracer.phases()}


def test_history_csv_is_the_references_run(fitted):
    d = os.path.join(fitted["model_path"], "autoencoders_latentFeatures")
    history = pd.read_csv(os.path.join(d, "history.csv"))
    assert list(history.columns) == ["epoch", "loss", "val_loss"] and list(history["epoch"]) == [0, 1, 2, 3]
    block = ref.standardised(fitted["df"].drop(columns=["label"]))
    want, trainable, running = ref.train(block, N, N // 2, epochs=4, batch=BATCH)
    np.testing.assert_allclose(history["loss"], want["loss"], rtol=2e-4)
    # inference reads the running means, which follow the biases that only rounding noise moves
    # (test_five_adam_steps...): a thousandth here, read 8e-4 and 1.3e-3 on two runs
    np.testing.assert_allclose(history["val_loss"], want["val_loss"], rtol=5e-3)
    assert history["loss"].iloc[-1] < history["loss"].iloc[0]
    with np.load(os.path.join(d, "model.npz")) as blob:  # and the saved weights are the reference's
        np.testing.assert_allclose(blob["bottleneck.w"], np.asarray(trainable["bottleneck"]["w"]), rtol=1e-3, atol=2e-5)
        np.testing.assert_allclose(blob["enc2.bn.var"], np.asarray(running["enc2"]["var"]), rtol=1e-4)
        assert blob["enc1.w"].shape == (N, 2 * N) and blob["bottleneck.w"].shape == (N, N // 2)
        saved = {k: blob[k] for k in blob.files}
    got = fitted["out"].to_pandas()
    assert list(got.columns) == ["label"] + [f"latent_{i}" for i in range(N // 2)]
    assert np.array_equal(got["label"].to_numpy(), fitted["df"]["label"].to_numpy())
    np.testing.assert_allclose(got.drop(columns=["label"]).to_numpy(), ref.forward64(saved, block), rtol=1e-4, atol=1e-4)
    # each fault moves the history by more than the program differs from the reference
    for fault in ({"running_in_training": True}, {"bias_correction": False}):
        faulty, _, _ = ref.train(block, N, N // 2, epochs=4, batch=BATCH, **fault)
        assert np.abs(faulty["loss"] / want["loss"] - 1).max() > 0.01, fault


def test_history_round_trips_through_save_and_load(fitted, tmp_path):
    ae, params = AutoEncoder.load(fitted["model_path"])
    first = pd.read_csv(os.path.join(fitted["model_path"], "autoencoders_latentFeatures", "history.csv"))
    assert ae.history is not None and ae.history["loss"].dtype == np.float32
    np.testing.assert_array_equal(ae.history["val_loss"].to_numpy(), first["val_loss"].to_numpy(np.float32))
    ae.save(params, str(tmp_path))
    d = tmp_path / "autoencoders_latentFeatures"
    with open(d / "history.csv", "rb") as a, open(os.path.join(fitted["model_path"], "autoencoders_latentFeatures",
                                                              "history.csv"), "rb") as b:
        assert a.read() == b.read()
    with open(d / "model.npz", "rb") as a, open(os.path.join(fitted["model_path"], "autoencoders_latentFeatures",
                                                             "model.npz"), "rb") as b:
        assert a.read() == b.read()  # no clock in the archive: a pass is held to the same bytes


def test_the_stage_rows_of_a_pass_carry_the_fits_counts(fitted):
    rows = [r for r in fitted["phases"] if r["name"].startswith("ae/")]
    assert [r["name"] for r in rows] == ["ae/prep", "ae/fit", "ae/apply", "ae/save"]
    assert all(r["parent"] == "dag" for r in rows)
    by = {r["name"]: r["counts"] for r in rows}
    want = ref.fit_arithmetic(300, N, N // 2, epochs=4, batch=BATCH)
    assert {k: by["ae/fit"][k] for k in ref.FIT_COUNTS} == want
    assert want == {"steps": 28, "epochs": 4, "batch": BATCH, "fit_rows": 240, "val_rows": 60,
                    "params": 2 * (N * 2 * N + 2 * N * N + N * N // 2) + 3 * 6 * N + N // 2 + N}
    assert by["ae/fit"]["flops_per_step"] == 6 * BATCH * AutoEncoder(N, N // 2).n_weights and by["ae/fit"]["bf16"] == 0
    padded = fitted["out"].padded_rows
    assert by["ae/prep"] == {"rows": padded, "cols": N}
    assert by["ae/apply"] == {"rows": padded, "cols": N, "latent": N // 2}
    assert all(r["end_s"] >= r["start_s"] for r in rows) and rows[1]["end_s"] <= rows[2]["start_s"]


def test_with_columns_gives_the_table_the_loop_gave():
    from anovos_tpu.shared.table import Column

    t = Table.from_pandas(pd.DataFrame({"a": [1.0, 2.0, 3.0], "b": [4.0, 5.0, 6.0]}))
    new = [(name, Column("num", t["a"].data * i, t["a"].mask, dtype_name="float")) for i, name in
           enumerate(["x", "b", "y"], start=2)]  # "b" replaces a column in its place
    looped = t
    for name, col in new:
        looped = looped.with_column(name, col)
    bulk = t.with_columns(iter(new))
    assert bulk.col_names == looped.col_names == ["a", "b", "x", "y"] and bulk.nrows == looped.nrows == 3
    assert all(bulk[c] is looped[c] for c in bulk.col_names) and t.col_names == ["a", "b"]
    pd.testing.assert_frame_equal(bulk.to_pandas(), looped.to_pandas())
