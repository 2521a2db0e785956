"""Vectorized client execution — one XLA dispatch per round.

The seed trained each selected client with an eager Python loop (N
clients × E epochs × B batches of separate jitted step calls).  This
module groups same-shape clients and runs their *entire* local training
through one ``jax.vmap``-of-``lax.scan`` dispatch:

  * each client's shuffled epoch schedule is materialised as an index
    matrix (replicating `data.loader.batches` draw-for-draw, so results
    match the per-client loop);
  * partial trailing batches are padded to the full batch size with a
    per-sample mask — the masked mean-CE loss makes padded samples
    contribute exactly zero gradient, so padding is numerically inert;
  * clients with the same (dataset size, sample shape, step count) stack
    into a ``(K, T, B, ...)`` batch and train under ``vmap`` over K, with
    per-client Adam states vmapped alongside the params;
  * K is padded up to a power-of-two bucket (duplicating the last
    client's stack; padded rows are discarded on the way out) so the
    compiled executable is reused across rounds whose cohort sizes
    differ — XLA compiles once per (bucket, step-shape), not once per K.

The controller feeds the resulting updates to the event engine as the
round's precomputed work cache; the per-client `ClientPool.work_fn` path
remains for incremental invocation and as the parity reference.

With the device pipeline enabled (``REPRO_DEVICE_PIPELINE``, default on)
the trained stack never leaves the device: `run_group_batch` flattens it
into the ``(K, P)`` ravel-layout matrix with one extra jitted dispatch
and hands downstream consumers a `core.device_batch.DeviceUpdateBatch` —
per-client pytrees and host loss scalars are materialized lazily.  The
flatten is a *separate* dispatch from the training jit on purpose: XLA
never gets the chance to rearrange training math around it, so enabling
the pipeline cannot perturb training numerics.

Multi-device (``mesh``): given a 1-axis ``("clients",)`` mesh
(`launch.mesh.make_clients_mesh`), the same vmapped scan runs under
``shard_map`` with the cohort (K) dim split across the mesh — each
device trains its slice of the bucket (per-client Adam states live on
the owning device because ``optimizer.init`` runs *inside* the mapped
body), and the (K, P) flatten inherits the row sharding, composing with
the P-sharded merge (`kernels/fed_agg.fed_agg_apply_sharded`) so a round
never funnels through one device.  A ``None`` or size-1 mesh takes the
*identical* single-device vmap code path — bitwise-inert by
construction, not by tolerance.

Overlapped dispatch (``REPRO_OVERLAP_DISPATCH``, default on): the group
dispatch is launched but not blocked on — JAX's async dispatch returns
unready device arrays, so event-engine bookkeeping, trace IO, and
scheduler `propose` for the round overlap device compute; the only host
syncs left are the existing single batched loss fetch and the merge
read-back.  ``0`` blocks right here until the trained stack is ready.
Virtual time never reads the wall clock, so traces are byte-identical
either way.

Profiler spans (core/spans.py): ``fl.stage`` (host gather ``fl.gather``
and host→device ``fl.put``), ``fl.dispatch`` (the enqueue of the
group-train and flatten programs), ``fl.package`` and ``fl.sync`` on
the loss fetch and the overlap-off block; inside the group-train
program the local step's parts carry the named scopes ``loss_grad``,
``proximal`` and ``optimizer``.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.flatten_util import ravel_pytree
from jax.sharding import NamedSharding, PartitionSpec as P

from ..analysis import gates
from ..core import spans
from ..core.device_batch import DeviceUpdateBatch, pipeline_enabled
from ..optim import apply_updates, proximal_grad
from ..sharding.rules import cohort_spec

Pytree = Any


def _batch_indices(n: int, batch_size: int, epochs: int,
                   rng: np.random.Generator) -> Tuple[np.ndarray, np.ndarray]:
    """(T, B) index + mask matrices reproducing `loader.batches` order.

    Trailing partial batches are padded with index 0 / mask 0.

    Vectorized: one ``rng.permuted`` over a tiled arange draws all E
    epoch permutations at once — bit-identical, draw-for-draw, to E
    sequential ``rng.permutation(n)`` calls (both reduce to E row-wise
    Fisher–Yates passes over the same bit stream), without the
    O(E·n/B) per-batch Python loop.
    """
    orders = rng.permuted(np.tile(np.arange(n), (epochs, 1)), axis=1)
    per_epoch = -(-n // batch_size)             # batches per epoch
    pad = per_epoch * batch_size - n
    if pad:
        orders = np.concatenate(
            [orders, np.zeros((epochs, pad), dtype=orders.dtype)], axis=1)
    idx = orders.reshape(epochs * per_epoch, batch_size)
    mask = np.ones((epochs, per_epoch * batch_size), dtype=np.float32)
    if pad:
        mask[:, n:] = 0.0
    return idx, mask.reshape(epochs * per_epoch, batch_size)


def _bucket(k: int, multiple: int = 1) -> int:
    """Next power of two ≥ k, rounded up to a ``multiple`` (the mesh
    device count) so the cohort dim always divides the ``clients`` axis.
    With ``multiple=1`` this is exactly the historical bucket."""
    b = 1 << (k - 1).bit_length() if k > 1 else 1
    if multiple > 1 and b % multiple:
        b = -(-b // multiple) * multiple
    return b


def _normalize_mesh(mesh):
    """A missing or size-1 mesh is *no* mesh: the executor falls back to
    the plain vmap path, keeping single-device runs bitwise-identical."""
    if mesh is None or int(mesh.size) <= 1:
        return None
    return mesh


class VectorizedExecutor:
    """Runs the local epochs of a group of clients as one vmapped scan."""

    def __init__(self, task, mesh=None):
        self.task = task
        self.mesh = _normalize_mesh(mesh)
        # (mu, mesh key) -> compiled group fn: a mesh change must never
        # reuse a function traced for a different device layout
        self._jit_cache: Dict[tuple, Any] = {}
        # stacked-tree → (K, P) ravel-layout flatten; its own dispatch so
        # the training jit's numerics are untouched by the pipeline
        self._flatten = jax.jit(self._flatten_stacked)
        self._unravel_cache: Dict[Any, Callable] = {}
        # recompile accounting: one entry per distinct dispatch signature
        # (mu + mesh shape + bucketed operand shapes).  compile_count
        # going flat across rounds is the "compilation is a non-event"
        # invariant the round-pipeline tests assert — tracked *per mesh*,
        # so switching device counts registers as new compiles instead of
        # silently reusing a stale bucket.
        self._dispatch_keys: set = set()
        self._compile_counts: Dict[Any, int] = {}

    # ------------------------------------------------------------------
    def configure_mesh(self, mesh) -> None:
        """Point subsequent dispatches at ``mesh`` (size-1 → vmap path).

        Compiled functions and dispatch keys are retained per mesh, so
        flipping back restores the previously compiled executables."""
        self.mesh = _normalize_mesh(mesh)

    def _mesh_key(self) -> Optional[tuple]:
        """Hashable mesh identity for jit-cache / compile accounting."""
        if self.mesh is None:
            return None
        return tuple(self.mesh.shape.items())

    @property
    def compile_count(self) -> int:
        """Compile count for the *current* mesh — the per-mesh invariant
        tests assert flat across rounds (a mesh switch starts its own
        counter instead of inflating this one)."""
        return self._compile_counts.get(self._mesh_key(), 0)

    @property
    def compile_count_total(self) -> int:
        """Cumulative compiles across every mesh this executor has used."""
        return sum(self._compile_counts.values())

    # ------------------------------------------------------------------
    def _group_fn(self, mu: float):
        """vmap-over-clients of scan-over-steps local training."""
        cache_key = (mu, self._mesh_key())
        if cache_key in self._jit_cache:
            return self._jit_cache[cache_key]
        task = self.task
        optimizer = task.optimizer

        def masked_loss(params, x, y, m):
            logits = task.model.apply(params, x)
            logp = jax.nn.log_softmax(logits)
            ce = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
            # identical to batch-mean CE when the mask is all ones
            return jnp.sum(ce * m) / jnp.maximum(jnp.sum(m), 1.0)

        def one_client(global_params, xs, ys, ms):
            opt_state = optimizer.init(global_params)

            # the named scopes label the step's parts in the HLO
            # metadata (and so in a device trace); the program is
            # otherwise unchanged
            def step(carry, batch):
                params, opt_state = carry
                x, y, m = batch
                with jax.named_scope("loss_grad"):
                    loss, grads = jax.value_and_grad(masked_loss)(
                        params, x, y, m)
                with jax.named_scope("proximal"):
                    grads = proximal_grad(grads, params, global_params, mu)
                with jax.named_scope("optimizer"):
                    updates, opt_state = optimizer.update(grads, opt_state,
                                                          params)
                    params = apply_updates(params, updates)
                return (params, opt_state), loss

            # XLA:CPU executes while-loops serially with poor fusion —
            # unrolling the (short) local-epoch scan is ~15x faster there
            # and harmless on TPU
            unroll = max(1, min(int(xs.shape[0]), 8))
            (params, _), losses = lax.scan(step, (global_params, opt_state),
                                           (xs, ys, ms), unroll=unroll)
            return params, jnp.mean(losses)

        cohort = jax.vmap(one_client, in_axes=(None, 0, 0, 0))
        if self.mesh is not None:
            # split the cohort (K) dim over the 'clients' axis: each
            # device vmaps its own slice, Adam states included (built by
            # optimizer.init inside the mapped body, so they never exist
            # unsharded); global params replicate.  check_vma=False —
            # the varying-axes analysis chokes on the scan carry.
            spec = cohort_spec()
            cohort = jax.shard_map(cohort, mesh=self.mesh,
                                   in_specs=(P(), spec, spec, spec),
                                   out_specs=(spec, spec), check_vma=False)
        # memoized per (mu, mesh) in _jit_cache (guard at the top), so
        # construction happens once per setting, not per round
        fn = jax.jit(cohort)  # repro-lint: disable=JAX003
        self._jit_cache[cache_key] = fn
        return fn

    # ------------------------------------------------------------------
    @staticmethod
    def _flatten_stacked(stacked: Pytree) -> jnp.ndarray:
        """(K, P) matrix whose row k is exactly
        ``ravel_pytree(tree_map(lambda l: l[k], stacked))[0]``: raveled
        leaves concatenated in tree order, cast to the promoted dtype."""
        leaves = jax.tree_util.tree_leaves(stacked)
        k = leaves[0].shape[0]
        dt = jnp.result_type(*[l.dtype for l in leaves])
        return jnp.concatenate(
            [l.reshape(k, -1).astype(dt) for l in leaves], axis=1)

    def _unravel_for(self, stacked: Pytree) -> Callable:
        """The shared row → pytree inverse (cached per tree structure)."""
        leaves, treedef = jax.tree_util.tree_flatten(stacked)
        key = (treedef,
               tuple((l.shape[1:], str(l.dtype)) for l in leaves))
        un = self._unravel_cache.get(key)
        if un is None:
            single = jax.tree_util.tree_unflatten(
                treedef, [jnp.zeros(l.shape[1:], l.dtype) for l in leaves])
            _, un = ravel_pytree(single)
            self._unravel_cache[key] = un
        return un

    def _place(self, arr: np.ndarray) -> jnp.ndarray:
        """Stage one stacked operand on device; with a mesh, pre-shard
        the K dim so the shard_map dispatch never reshards inputs."""
        if self.mesh is None:
            return jnp.asarray(arr)
        return jax.device_put(arr, NamedSharding(self.mesh, cohort_spec()))

    def _stage(self, datasets, seeds, round_number: int):
        """Host gather of each client's (T, B) batches, padded to the
        bucket, placed on device: -> (xs, ys, ms) device operands.  The
        ``fl.stage`` span covers it; ``bytes`` is what crosses."""
        cfg = self.task.config
        devices = int(self.mesh.size) if self.mesh is not None else 1
        bucket = _bucket(len(datasets), devices)
        with spans.span("fl.stage", round=round_number,
                        clients=len(datasets), bucket=bucket) as stage:
            with spans.span("fl.gather"):
                xs, ys, ms = [], [], []
                for ds, seed in zip(datasets, seeds):
                    rng = np.random.default_rng(seed)
                    idx, mask = _batch_indices(len(ds), cfg.batch_size,
                                               cfg.epochs, rng)
                    xs.append(ds.x[idx])        # (T, B, ...)
                    ys.append(ds.y[idx])
                    ms.append(mask)
                xs, ys, ms = np.stack(xs), np.stack(ys), np.stack(ms)
                pad = bucket - len(datasets)
                if pad:
                    xs = np.concatenate([xs, np.repeat(xs[-1:], pad, axis=0)])
                    ys = np.concatenate([ys, np.repeat(ys[-1:], pad, axis=0)])
                    ms = np.concatenate([ms, np.repeat(ms[-1:], pad, axis=0)])
            nbytes = xs.nbytes + ys.nbytes + ms.nbytes
            stage.set_metadata(bytes=nbytes)
            spans.count("staged_bytes", nbytes)
            with spans.span("fl.put", bytes=nbytes):
                return self._place(xs), self._place(ys), self._place(ms)

    def _dispatch_key(self, mu: float, xs, ys) -> bool:
        """Record the dispatch signature; True when it is new (a
        compile for the current mesh)."""
        mesh_key = self._mesh_key()
        key = (mu, mesh_key, xs.shape, str(xs.dtype), ys.shape,
               str(ys.dtype))
        if key in self._dispatch_keys:
            return False
        self._dispatch_keys.add(key)
        self._compile_counts[mesh_key] = \
            self._compile_counts.get(mesh_key, 0) + 1
        spans.count("compiles")
        return True

    def _train_group(self, datasets, global_params: Pytree, mu: float,
                     seeds: Sequence[int], round_number: int = 0,
                     flatten: bool = False):
        """One bucketed vmap dispatch: (stacked out_params, losses) with
        K padded to the power-of-two bucket (rows ≥ len(cids) are pads;
        on a mesh the bucket also rounds up to the device count).  With
        ``flatten`` the (K, P) flat matrix is enqueued too and returned
        third."""
        xs, ys, ms = self._stage(datasets, seeds, round_number)
        new_shape = self._dispatch_key(mu, xs, ys)
        if self.mesh is not None:
            # replicate over the mesh: an unsharded merge leaves the
            # params committed to one device, which the dispatch refuses
            global_params = jax.device_put(global_params,
                                           NamedSharding(self.mesh, P()))
        with spans.span("fl.dispatch", round=round_number,
                        bucket=int(xs.shape[0]), new_shape=int(new_shape)):
            out_params, losses = self._group_fn(mu)(global_params, xs, ys,
                                                    ms)
            if not flatten:
                return out_params, losses
            return out_params, losses, self._flatten(out_params)

    def run_group(self, cids: Sequence[str], datasets, global_params: Pytree,
                  mu: float, seeds: Sequence[int], round_number: int = 0
                  ) -> Dict[str, Tuple[Pytree, float]]:
        """Train one same-shape group; returns cid -> (params, mean loss)."""
        out_params, losses = self._train_group(datasets, global_params, mu,
                                               seeds, round_number)
        # one batched transfer for the whole loss vector — K per-scalar
        # float(losses[k]) syncs were K blocking round-trips
        with spans.sync("loss", losses.nbytes):
            losses_np = np.asarray(losses)
        results = {}
        for k, cid in enumerate(cids):
            params_k = jax.tree_util.tree_map(lambda l: l[k], out_params)
            results[cid] = (params_k, float(losses_np[k]))
        return results

    def run_group_batch(self, cids: Sequence[str], datasets,
                        global_params: Pytree, mu: float,
                        seeds: Sequence[int],
                        round_number: int = 0) -> DeviceUpdateBatch:
        """Device-pipeline twin of `run_group`: the trained stack is
        flattened on device into the (K_bucket, P) ravel-layout matrix
        and returned as a DeviceUpdateBatch — nothing crosses to the
        host until a consumer materializes a row.  On a mesh the matrix
        rows stay sharded over 'clients', ready for the sharded merge."""
        out_params, losses, mat = self._train_group(
            datasets, global_params, mu, seeds, round_number, flatten=True)
        return DeviceUpdateBatch(mat, cids, self._unravel_for(out_params),
                                 losses=losses)

    # ------------------------------------------------------------------
    def _group(self, pool, cids: Sequence[str]) -> Dict[tuple, List[str]]:
        """Bucket clients by (dataset size, sample shape, dtype)."""
        groups: Dict[tuple, List[str]] = {}
        for cid in cids:
            ds = pool.clients[cid].dataset
            key = (len(ds), ds.x.shape[1:], str(ds.x.dtype))
            groups.setdefault(key, []).append(cid)
        return groups

    def warmup(self, pool, cids: Sequence[str], global_params: Pytree,
               round_number: int = 0) -> int:
        """Compile the train (and flatten) dispatches for the bucket
        shapes `cids` would use, without touching any round state — no
        packaging, no compressor residuals, results discarded.  Returns
        the executor's compile count for the current mesh."""
        for group_cids in self._group(pool, cids).values():
            datasets = [pool.clients[c].dataset for c in group_cids]
            seeds = [pool.client_seed(c, round_number) for c in group_cids]
            flatten = pipeline_enabled()
            out = self._train_group(datasets, global_params,
                                    pool.proximal_mu, seeds, round_number,
                                    flatten=flatten)
            if flatten:
                out[2].block_until_ready()
        return self.compile_count

    def run_clients(self, pool, cids: Sequence[str], global_params: Pytree,
                    round_number: int) -> Dict[str, tuple]:
        """Group → train → package: cid -> (ClientUpdate, nominal_work_s),
        the same contract as `ClientPool.work_fn` per client.

        Pipeline on: each group's updates stay on device as one
        DeviceUpdateBatch and the packaged ClientUpdates are thin row
        views — and unless ``REPRO_OVERLAP_DISPATCH=0`` the dispatch is
        *not* blocked on, so the caller's bookkeeping overlaps device
        compute.  Pipeline off (``REPRO_DEVICE_PIPELINE=0``): the legacy
        per-client materialize → package path (inherently synchronous)."""
        results: Dict[str, tuple] = {}
        overlap = gates.overlap_dispatch_enabled()
        for group_cids in self._group(pool, cids).values():
            datasets = [pool.clients[c].dataset for c in group_cids]
            seeds = [pool.client_seed(c, round_number) for c in group_cids]
            if pipeline_enabled():
                batch = self.run_group_batch(group_cids, datasets,
                                             global_params,
                                             pool.proximal_mu, seeds,
                                             round_number)
                if not overlap:
                    with spans.sync("block", 0):
                        jax.block_until_ready((batch.mat, batch._losses))
                with spans.span("fl.package", round=round_number,
                                clients=len(group_cids)):
                    for i, cid in enumerate(group_cids):
                        ds = pool.clients[cid].dataset
                        update = pool.package_update(cid, None, round_number,
                                                     global_params,
                                                     batch=batch, row=i)
                        results[cid] = (update,
                                        self.task.nominal_work_seconds(ds))
                continue
            trained = self.run_group(group_cids, datasets, global_params,
                                     pool.proximal_mu, seeds, round_number)
            with spans.span("fl.package", round=round_number,
                            clients=len(group_cids)):
                for cid in group_cids:
                    params, _loss = trained[cid]
                    ds = pool.clients[cid].dataset
                    # pool.package_update runs the optional compression
                    # stage (same hook as the eager work_fn path)
                    update = pool.package_update(cid, params, round_number,
                                                 global_params)
                    results[cid] = (update,
                                    self.task.nominal_work_seconds(ds))
        return results
