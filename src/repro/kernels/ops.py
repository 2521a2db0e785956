"""jit'd public wrappers around the Pallas kernels.

Interpret mode is decided at every call (`resolve_interpret`): on CPU the
kernels run with interpret=True (the kernel body executes via the
interpreter, validating logic + BlockSpec tiling); on TPU they lower to
Mosaic.  REPRO_PALLAS_INTERPRET=0/1 or the per-call `interpret` arg
override the backend's choice, except that interpret mode on a TPU
backend raises: a kernel that silently ran in the interpreter on the
chip would hide the device from every measurement.

The single-device wrappers take their array arguments onto one device
first (`on_one_device`): Mosaic cannot partition a kernel outside
`shard_map`, and the multi-device executor hands over a (K, P) matrix
whose rows are spread over its mesh.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..analysis import gates
from .compress import int8_decode as _int8_decode
from .compress import int8_encode as _int8_encode
from .compress import topk_decode as topk_decode  # noqa: F401 (re-export)
from .compress import topk_encode as _topk_encode
from .compress import topk_mask as _topk_mask
from .fed_agg import fed_agg as _fed_agg
from .fed_agg import fed_agg_apply as _fed_agg_apply
from .fed_agg import fed_agg_apply_sharded as _fed_agg_apply_sharded
from .fed_agg import fed_agg_sharded as _fed_agg_sharded
from .flash_attention import flash_attention as _flash_attention
from .ssd_scan import ssd_scan as _ssd_scan


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """The interpret flag for one kernel call: the explicit argument, else
    the REPRO_PALLAS_INTERPRET override, else interpret on CPU only.

    Raises ``RuntimeError`` when that resolves to interpret mode on a TPU
    backend."""
    backend = jax.default_backend()
    if interpret is None:
        interpret = gates.pallas_interpret_override()
    if interpret is None:
        interpret = backend == "cpu"
    if interpret and backend == "tpu":
        raise RuntimeError(
            "Pallas interpret mode was requested on a TPU backend "
            "(REPRO_PALLAS_INTERPRET=1 or interpret=True); unset it so "
            "the kernels lower to Mosaic")
    return interpret


def on_one_device(x):
    """`x` gathered onto the default device when it spans several (a
    Mosaic kernel outside `shard_map` refuses such an input at lowering);
    single-device arrays and tracers pass through unchanged."""
    if (isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer)
            and len(x.sharding.device_set) > 1):
        return jax.device_put(x, jax.devices()[0])
    return x


def fed_agg(updates: jnp.ndarray, coeffs: jnp.ndarray,
            tile_p: int = 2048,
            interpret: Optional[bool] = None,
            donate: bool = False) -> jnp.ndarray:
    return _fed_agg(on_one_device(updates), on_one_device(coeffs),
                    tile_p=tile_p,
                    interpret=resolve_interpret(interpret),
                    donate=donate)


def fed_agg_apply(updates: jnp.ndarray, coeffs: jnp.ndarray,
                  params: jnp.ndarray, m: jnp.ndarray, v: jnp.ndarray,
                  lr, mix, b1, b2, eps, *, opt: str = "fedadam",
                  tile_p: int = 2048, interpret: Optional[bool] = None,
                  donate: bool = False):
    updates, coeffs, params, m, v = map(on_one_device,
                                       (updates, coeffs, params, m, v))
    return _fed_agg_apply(
        updates, coeffs, params, m, v, lr, mix, b1, b2, eps, opt=opt,
        tile_p=tile_p,
        interpret=resolve_interpret(interpret),
        donate=donate)


def fed_agg_sharded(updates: jnp.ndarray, coeffs: jnp.ndarray, mesh,
                    tile_p: int = 2048,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    return _fed_agg_sharded(
        updates, coeffs, mesh, tile_p=tile_p,
        interpret=resolve_interpret(interpret))


def fed_agg_apply_sharded(updates: jnp.ndarray, coeffs: jnp.ndarray,
                          params: jnp.ndarray, m: jnp.ndarray,
                          v: jnp.ndarray, lr, mix, b1, b2, eps, *,
                          opt: str = "fedadam", mesh, tile_p: int = 2048,
                          interpret: Optional[bool] = None):
    return _fed_agg_apply_sharded(
        updates, coeffs, params, m, v, lr, mix, b1, b2, eps, opt=opt,
        mesh=mesh, tile_p=tile_p,
        interpret=resolve_interpret(interpret))


def int8_encode(x: jnp.ndarray, chunk: int = 256, tile_r: int = 8,
                interpret: Optional[bool] = None):
    return _int8_encode(on_one_device(x), chunk=chunk, tile_r=tile_r,
                        interpret=resolve_interpret(interpret))


def int8_decode(q: jnp.ndarray, scale: jnp.ndarray, length: int,
                tile_r: int = 8,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    return _int8_decode(on_one_device(q), on_one_device(scale), length,
                        tile_r=tile_r, interpret=resolve_interpret(interpret))


def topk_encode(x: jnp.ndarray, k: int, tile_p: int = 2048,
                interpret: Optional[bool] = None):
    return _topk_encode(on_one_device(x), k, tile_p=tile_p,
                        interpret=resolve_interpret(interpret))


def topk_mask(x: jnp.ndarray, tau, last_keep, tile_p: int = 2048,
              interpret: Optional[bool] = None) -> jnp.ndarray:
    return _topk_mask(on_one_device(x), tau, last_keep, tile_p=tile_p,
                      interpret=resolve_interpret(interpret))


def flash_attention(q, k, v, causal: bool = True,
                    window: Optional[int] = None, softcap: float = 0.0,
                    bq: int = 128, bk: int = 128,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    return _flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap, bq=bq, bk=bk,
        interpret=resolve_interpret(interpret))


def ssd_scan(x, a_dt, B, C, chunk: int = 128,
             interpret: Optional[bool] = None) -> jnp.ndarray:
    return _ssd_scan(x, a_dt, B, C, chunk=chunk,
                     interpret=resolve_interpret(interpret))
