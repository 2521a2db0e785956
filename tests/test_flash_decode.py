"""Sequence-sharded flash-decoding: combine math vs unsharded oracle.

The single-device case exercises the shard_map path trivially; the real
multi-shard combine is validated in a subprocess with 8 forced host
devices (the device count must be set before jax initialises).
"""
import subprocess
import sys
import textwrap
from pathlib import Path

import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import make_host_mesh
from repro.sharding.flash_decode import (reference_decode_attention,
                                         sharded_decode_attention)

REPO_ROOT = Path(__file__).resolve().parents[1]


def test_single_shard_matches_oracle():
    rng = np.random.default_rng(0)
    B, H, K, S, hd = 2, 8, 4, 64, 32
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(B, K, S, hd)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(B, K, S, hd)), jnp.float32)
    pos = jnp.asarray([10, 63], jnp.int32)
    mesh = make_host_mesh()
    with mesh:
        got = sharded_decode_attention(q, kc, vc, pos, mesh)
    want = reference_decode_attention(q, kc, vc, pos)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


MULTI_DEVICE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from repro.sharding.flash_decode import (reference_decode_attention,
                                             sharded_decode_attention)
    mesh = jax.make_mesh((2, 4), ("data", "model"))
    rng = np.random.default_rng(0)
    B, H, K, S, hd = 4, 8, 4, 128, 16
    q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(B, K, S, hd)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(B, K, S, hd)), jnp.float32)
    pos = jnp.asarray([5, 64, 100, 127], jnp.int32)
    with mesh:
        got = sharded_decode_attention(q, kc, vc, pos, mesh)
    want = reference_decode_attention(q, kc, vc, pos)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)
    # the lowered HLO must NOT all-gather the KV cache
    from jax.sharding import NamedSharding, PartitionSpec as P
    with mesh:
        f = jax.jit(lambda q_, k_, v_, p_: sharded_decode_attention(
            q_, k_, v_, p_, mesh))
        hlo = f.lower(q, kc, vc, pos).compile().as_text()
    kv_bytes = B * K * S * hd * 4
    import re
    for line in hlo.splitlines():
        if "all-gather(" in line:
            m = re.search(r"f32\\[([0-9,]+)\\]", line)
            if m:
                n = 1
                for d in m.group(1).split(","):
                    n *= int(d)
                assert n * 4 < kv_bytes / 2, f"KV gather detected: {line[:120]}"
    print("MULTI-OK")
""")


def test_multi_shard_combine_subprocess():
    res = subprocess.run([sys.executable, "-c", MULTI_DEVICE_SCRIPT],
                         capture_output=True, text=True, timeout=600,
                         cwd=str(REPO_ROOT))
    assert "MULTI-OK" in res.stdout, res.stdout + res.stderr
