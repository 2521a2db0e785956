"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (APPLY_OPTS, fed_agg, fed_agg_apply,
                           fed_agg_apply_sharded, fed_agg_sharded,
                           flash_attention, ssd_scan, topk_mask)
from repro.kernels.ref import (fed_agg_apply_ref, fed_agg_ref,
                               flash_attention_ref, ssd_ref, topk_ref)
from repro.launch.mesh import make_host_mesh

RNG = np.random.default_rng(0)


# ------------------------------------------------------------- fed_agg
@pytest.mark.parametrize("K,P", [(1, 16), (4, 1000), (16, 4096), (7, 333)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fed_agg_matches_ref(K, P, dtype):
    u = jnp.asarray(RNG.normal(size=(K, P)), dtype)
    c = jnp.asarray(RNG.random(K), jnp.float32)
    got = fed_agg(u, c, tile_p=512)
    want = fed_agg_ref(u, c)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_fed_agg_eq3_coefficients():
    """Aggregating 3 identical updates with Eq.3 coeffs == scaled update."""
    P = 256
    w = jnp.asarray(RNG.normal(size=(P,)), jnp.float32)
    u = jnp.stack([w, w, w])
    c = jnp.asarray([0.5, 0.3, 0.2])
    np.testing.assert_allclose(fed_agg(u, c), w, rtol=1e-5)


# ------------------------------------------------------- fed_agg_apply
@pytest.mark.parametrize("opt", APPLY_OPTS)
@pytest.mark.parametrize("K,P", [(4, 1000), (7, 333)])
def test_fed_agg_apply_matches_ref(opt, K, P):
    u = jnp.asarray(RNG.normal(size=(K, P)), jnp.float32)
    c = jnp.asarray(RNG.random(K), jnp.float32)
    g = jnp.asarray(RNG.normal(size=(P,)), jnp.float32)
    m = jnp.asarray(RNG.normal(size=(P,)) * 0.1, jnp.float32)
    v = jnp.asarray(np.abs(RNG.normal(size=(P,))) * 0.1, jnp.float32)
    args = (0.1, 0.8, 0.9, 0.99, 1e-3)          # lr, mix, b1, b2, eps
    got = fed_agg_apply(u, c, g, m, v, *args, opt=opt, tile_p=512)
    want = fed_agg_apply_ref(u, c, g, m, v, *args, opt=opt)
    for got_x, want_x in zip(got, want):
        np.testing.assert_allclose(np.asarray(got_x), np.asarray(want_x),
                                   rtol=1e-5, atol=1e-5)


def test_fed_agg_sharded_matches_ref():
    """Mesh dispatch (P-dim shards) against the unsharded oracle."""
    mesh = make_host_mesh()
    K, P = 5, 777
    u = jnp.asarray(RNG.normal(size=(K, P)), jnp.float32)
    c = jnp.asarray(RNG.random(K), jnp.float32)
    got = fed_agg_sharded(u, c, mesh, tile_p=256)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(fed_agg_ref(u, c)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("opt", ["sgd", "fedadam"])
def test_fed_agg_apply_sharded_matches_ref(opt):
    mesh = make_host_mesh()
    K, P = 4, 513
    u = jnp.asarray(RNG.normal(size=(K, P)), jnp.float32)
    c = jnp.asarray(RNG.random(K), jnp.float32)
    g = jnp.asarray(RNG.normal(size=(P,)), jnp.float32)
    m = jnp.zeros((P,), jnp.float32)
    v = jnp.zeros((P,), jnp.float32)
    args = (0.05, 1.0, 0.9, 0.99, 1e-3)
    got = fed_agg_apply_sharded(u, c, g, m, v, *args, opt=opt,
                                mesh=mesh, tile_p=256)
    want = fed_agg_apply_ref(u, c, g, m, v, *args, opt=opt)
    for got_x, want_x in zip(got, want):
        np.testing.assert_allclose(np.asarray(got_x), np.asarray(want_x),
                                   rtol=1e-5, atol=1e-5)


# ----------------------------------------------------------- topk_mask
@pytest.mark.parametrize("P,k", [(1000, 10), (333, 333), (4096, 41)])
def test_topk_mask_matches_ref(P, k):
    """The threshold-mask decode equals the top_k+scatter oracle,
    including the lowest-index-wins tie-break."""
    x = jnp.asarray(RNG.normal(size=(P,)), jnp.float32)
    _, _, want = topk_ref(x, k)
    mags, idx = jax.lax.top_k(jnp.abs(x), min(k, P))
    tau = mags[min(k, P) - 1]
    last_keep = jnp.max(jnp.where(mags == tau, idx, -1)).astype(jnp.int32)
    got = topk_mask(x, tau, last_keep, tile_p=256)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=0)


def test_topk_mask_tie_break():
    """Equal magnitudes: the kernel must keep the lowest indices, exactly
    like lax.top_k (the wire format the decode path reconstructs)."""
    x = jnp.asarray([1.0, -1.0, 1.0, 0.5, -1.0, 0.25], jnp.float32)
    k = 2
    _, _, want = topk_ref(x, k)
    mags, idx = jax.lax.top_k(jnp.abs(x), k)
    tau = mags[k - 1]
    last_keep = jnp.max(jnp.where(mags == tau, idx, -1)).astype(jnp.int32)
    got = topk_mask(x, tau, last_keep, tile_p=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))


# ------------------------------------------------------------- attention
@pytest.mark.parametrize("B,H,Hkv,S,d", [
    (1, 2, 2, 128, 32), (2, 4, 2, 256, 64), (1, 8, 1, 192, 32),
    (1, 2, 2, 100, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(B, H, Hkv, S, d, dtype):
    q = jnp.asarray(RNG.normal(size=(B, H, S, d)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, Hkv, S, d)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, Hkv, S, d)), dtype)
    got = flash_attention(q, k, v, bq=64, bk=64)
    want = flash_attention_ref(q, k, v)
    tol = 2e-5 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("window", [32, 64])
def test_flash_attention_window(window):
    q = jnp.asarray(RNG.normal(size=(1, 2, 160, 32)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, 2, 160, 32)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, 2, 160, 32)), jnp.float32)
    got = flash_attention(q, k, v, window=window, bq=64, bk=64)
    want = flash_attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_flash_attention_softcap():
    q = jnp.asarray(RNG.normal(size=(1, 2, 128, 32)) * 3, jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, 2, 128, 32)) * 3, jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, 2, 128, 32)), jnp.float32)
    got = flash_attention(q, k, v, softcap=20.0, bq=64, bk=64)
    want = flash_attention_ref(q, k, v, softcap=20.0)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # and it must differ from the uncapped result
    uncapped = flash_attention_ref(q, k, v)
    assert float(jnp.max(jnp.abs(want - uncapped))) > 1e-4


# ------------------------------------------------------------- ssd
@pytest.mark.parametrize("b,l,h,p,n,chunk", [
    (1, 64, 2, 16, 8, 32), (2, 128, 4, 32, 16, 64), (1, 96, 1, 8, 4, 32),
    (1, 256, 2, 64, 128, 128),
])
def test_ssd_scan_matches_sequential(b, l, h, p, n, chunk):
    x = jnp.asarray(RNG.normal(size=(b, l, h, p)) * 0.5, jnp.float32)
    a = jnp.asarray(-np.abs(RNG.normal(size=(b, l, h))) * 0.3, jnp.float32)
    B = jnp.asarray(RNG.normal(size=(b, l, h, n)) * 0.5, jnp.float32)
    C = jnp.asarray(RNG.normal(size=(b, l, h, n)) * 0.5, jnp.float32)
    got = ssd_scan(x, a, B, C, chunk=chunk)
    want = ssd_ref(x, a, B, C)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_ssd_scan_bf16():
    x = jnp.asarray(RNG.normal(size=(1, 64, 2, 16)) * 0.5, jnp.bfloat16)
    a = jnp.asarray(-np.abs(RNG.normal(size=(1, 64, 2))) * 0.3, jnp.float32)
    B = jnp.asarray(RNG.normal(size=(1, 64, 2, 8)) * 0.5, jnp.bfloat16)
    C = jnp.asarray(RNG.normal(size=(1, 64, 2, 8)) * 0.5, jnp.bfloat16)
    got = ssd_scan(x, a, B, C, chunk=32)
    want = ssd_ref(x, a, B, C)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=6e-2, atol=6e-2)


def test_ssd_state_continuity_vs_model_path():
    """The model's jnp chunked SSD must agree with the kernel for the
    same inputs (two independent chunked implementations)."""
    from repro.models.ssm import ssd_chunked
    x = jnp.asarray(RNG.normal(size=(1, 128, 2, 16)) * 0.5, jnp.float32)
    a = jnp.asarray(-np.abs(RNG.normal(size=(1, 128, 2))) * 0.3, jnp.float32)
    B = jnp.asarray(RNG.normal(size=(1, 128, 2, 8)) * 0.5, jnp.float32)
    C = jnp.asarray(RNG.normal(size=(1, 128, 2, 8)) * 0.5, jnp.float32)
    y1 = ssd_scan(x, a, B, C, chunk=32)
    y2, _ = ssd_chunked(x, a, B, C, chunk=64)
    np.testing.assert_allclose(y1, y2, rtol=2e-4, atol=2e-4)


# ------------------------------------------------------ interpret mode
def test_interpret_mode_resolved_per_call(monkeypatch):
    """The interpret flag is read at every call (no import-time latch):
    explicit arg, else REPRO_PALLAS_INTERPRET, else the backend."""
    from repro.analysis import gates
    from repro.kernels.ops import resolve_interpret

    monkeypatch.delenv(gates.PALLAS_INTERPRET, raising=False)
    assert resolve_interpret() is (jax.default_backend() == "cpu")
    monkeypatch.setenv(gates.PALLAS_INTERPRET, "0")
    assert resolve_interpret() is False
    monkeypatch.setenv(gates.PALLAS_INTERPRET, "1")
    assert resolve_interpret() is True
    assert resolve_interpret(False) is False


def test_forced_interpret_on_tpu_backend_raises(monkeypatch):
    """On a TPU backend the kernels lower to Mosaic; forcing the
    interpreter there raises instead of silently bypassing the chip."""
    from repro.analysis import gates
    from repro.kernels import ops

    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv(gates.PALLAS_INTERPRET, raising=False)
    assert ops.resolve_interpret() is False
    with pytest.raises(RuntimeError, match="interpret"):
        ops.resolve_interpret(True)
    monkeypatch.setenv(gates.PALLAS_INTERPRET, "1")
    with pytest.raises(RuntimeError, match="interpret"):
        ops.fed_agg(jnp.ones((2, 8)), jnp.ones(2))
