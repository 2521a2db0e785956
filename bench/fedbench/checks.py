"""The comparison that decides `correct`.

For the window's last study it takes the sampled round t and eval round
e that the probes kept, and computes, for an implementation under test
(the program, or a reference put in its place: the bfloat16 control, a
planted fault, or the reference at another matmul precision):

  update_gap     local training: over the clients trained in round t, the
                 worst client's worst leaf of ||d_impl - d_ref|| /
                 max(||d_ref||, median leaf ||d_ref||), d = params after
                 local training - params sent;
  loss_gap       local training: the worst client's |mean training loss
                 - reference| / reference;
  cohort_loss_gap  local training: |the round's summed mean training
                 losses - reference| / reference, over the same clients
                 (steadier than one client's where local training
                 magnifies rounding, as FEMNIST's does: PERF.md);
  merge_gap      the merge: params after round t against the reference's
                 Eq. 3 weighted sum of the updates the executor produced
                 for the clients that should merge in round t, by the
                 same worst-leaf measure on the change from the params
                 sent (the updates themselves are the training numbers'
                 to check);
  eval_loss_gap  evaluation: over the study's reported evaluations of round
                 e (and the final one when e is the last round), the worst
                 |test-size-weighted mean loss - reference| / reference,
                 on the params carried out of round e;
  eval_acc_gap   evaluation: the worst |accuracy the study reports -
                 reference| (each reported accuracy is the test-size
                 weighted mean over its sample of clients, so one
                 prediction that flips on a near-tie moves it by one
                 sample of the whole sample, not of one client).

Which updates merge in round t: the clients that answered in time and the
stragglers of round t - 1 that arrived in round t (tau = 2 leaves older
ones out).  Whether a client answers in time, and when a late one
arrives, is decided by the virtual clock (cold starts, instance speeds,
platform failures and retries), which the round records report.  Who may
be in either list is decided by the scenario alone (scenario.py): a
crashing client never answers, and every merged update was trained in
the round that produced it.  A record that breaks this fails the check.

The merge is compared on the updates the executor produced: where local
training magnifies rounding (FEMNIST), a merge of the reference's own
updates differs from the program's by the training's spread, not by the
merge's (PERF.md, Findings).

The reference runs at float32 with the matmul precision the
configuration states.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from . import reference as R
from . import scenario

NUMBERS = ("update_gap", "loss_gap", "cohort_loss_gap", "merge_gap",
           "eval_loss_gap", "eval_acc_gap")
# what can stand in the program's place: dtype, matmul precision (None:
# the configuration's), and a planted fault of local training
IMPLS = {"control": (jnp.bfloat16, None, None),
         "half_batch": (R.F32, None, "half_batch"),
         "unchanged": (R.F32, None, "frozen"),
         "highest": (R.F32, "highest", None)}


def leaf_gap(d_impl, d_ref) -> float:
    """Worst leaf of ||a - b|| / max(||b||, median leaf ||b||)."""
    a = jax.tree_util.tree_leaves(d_impl)
    b = jax.tree_util.tree_leaves(d_ref)
    diff = [float(jnp.linalg.norm((x - y).ravel())) for x, y in zip(a, b)]
    ref = [float(jnp.linalg.norm(y.ravel())) for y in b]
    floor = float(np.median(ref))
    if floor == 0.0:
        return 0.0 if max(diff) == 0.0 else float("inf")
    return max(d / max(r, floor) for d, r in zip(diff, ref))


def _sub(a, b):
    return jax.tree_util.tree_map(lambda x, y: x - y, a, b)


def _rel(got: float, want: float) -> float:
    """|got - want| / want; a loss of exactly 0 has only itself near."""
    if want == 0.0:
        return 0.0 if got == 0.0 else float("inf")
    return abs(got - want) / want


class MergeRecordError(RuntimeError):
    """The round records merge an update the scenario does not allow."""


def merge_entries(rounds, t: int, tau: int, trained, profile):
    """-> [(client id, producing round)] of the updates that merge in
    round t.  trained: round -> the clients trained in it (as far as the
    probes kept them); profile: client id -> scenario kind."""
    st = rounds[t]
    entries = []
    for cid in st.successes:
        if cid not in trained[t] or profile[cid] == scenario.CRASH:
            raise MergeRecordError(f"{cid} ({profile[cid]}) answered in "
                                   f"time in round {t}")
        entries.append((cid, t))
    for cid in st.straggler_arrivals:
        p = max((q for q in range(t) if cid in rounds[q].late), default=None)
        if p is None or profile[cid] == scenario.CRASH:
            raise MergeRecordError(f"{cid} ({profile[cid]}) arrived in "
                                   f"round {t} but was never late")
        if t - p < tau:
            if cid not in trained.get(p, ()):
                raise MergeRecordError(f"{cid} arrived from round {p}, "
                                       f"where it was not trained")
            entries.append((cid, p))
    return entries


class Checker:
    """Compares one study's captured rounds with the reference."""

    def __init__(self, ref_module, cfg: dict, traffic: dict, train: dict,
                 test: dict, study_seed: int, precision=None):
        """train / test: client id -> the dataset handed to the program
        (its numpy arrays `x` and `y`); study_seed: the seed the study ran with (its scenario's draw);
        precision: the reference's matmul precision, by default the
        configuration's."""
        self.ref, self.cfg, self.traffic = ref_module, cfg, traffic
        self.train, self.test = train, test
        self.precision = precision or cfg["precision"]["matmul"]
        self.study_seed = study_seed
        self.trainers: Dict = {}
        self.evaluators: Dict = {}
        # (dtype, fault, precision, round, client) -> (params, loss);
        # checkers of one study may share it
        self.trained: Dict = {}
        self.worst: Dict[str, float] = {}
        self.median: Dict[str, float] = {}
        self.merge_error = None

    def _impl(self, impl: str):
        dtype, prec, fault = IMPLS.get(impl, (R.F32, None, None))
        return dtype, prec or self.precision, fault

    def _updates(self, impl: str, params, rnd: int, cap, cids):
        """Local training of `cids` of round `rnd` from `params` by the
        reference ("reference") or a stand-in, cached per client."""
        dtype, prec, fault = self._impl(impl)
        key = (dtype, fault)
        if key not in self.trainers:
            self.trainers[key] = R.make_trainer(
                self.ref.apply, self.cfg, dtype,
                half_batch=fault == "half_batch", frozen=fault == "frozen")
        todo = [c for c in cids if (key, prec, rnd, c) not in self.trained]
        if todo:
            done = R.train_clients(
                self.trainers[key], params,
                [(self.train[c].x, self.train[c].y, cap.seeds[rnd][c])
                 for c in todo], self.cfg, dtype, prec)
            for c, r in zip(todo, done):
                self.trained[(key, prec, rnd, c)] = r
        return [self.trained[(key, prec, rnd, c)] for c in cids]

    def _evaluate(self, impl: str, params, ds):
        dtype, prec, _ = self._impl(impl)
        if dtype not in self.evaluators:
            self.evaluators[dtype] = R.make_evaluator(self.ref.apply,
                                                      self.cfg, dtype)
        return R.evaluate(self.evaluators[dtype], params, ds.x, ds.y, dtype,
                          prec)

    # ------------------------------------------------------------------
    def numbers(self, cap, impl: str = "program") -> Dict[str, float]:
        """The five numbers for `impl`: "program", or one of IMPLS put in
        the program's place ("control": the bfloat16 reference;
        "half_batch": the reference with half of every batch left out in
        local training; "highest": the reference at `highest` matmul
        precision)."""
        if impl != "program" and impl not in IMPLS:
            raise ValueError(impl)
        program = impl == "program"
        res = cap.result
        t, e = cap.round_t, cap.round_e
        n_rounds = len(res.rounds)
        g_t = cap.params[t]
        g_next = (cap.params[t + 1] if t + 1 < n_rounds
                  else res.final_params)
        _, unravel = ravel_pytree(g_t)
        out: Dict[str, float] = {}

        # ---- local training of round t --------------------------------
        cids = sorted(cap.seeds[t])
        ref = self._updates("reference", g_t, t, cap, cids)
        if program:
            imp = [(unravel(b.row(r)), b.loss(r))
                   for b, r in (cap.rows[t][c] for c in cids)]
        else:
            imp = self._updates(impl, g_t, t, cap, cids)
        upd = [leaf_gap(_sub(ip, g_t), _sub(rp, g_t))
               for (ip, _), (rp, _) in zip(imp, ref)]
        loss = [_rel(il, rl) for (_, il), (_, rl) in zip(imp, ref)]
        out["update_gap"], out["loss_gap"] = max(upd), max(loss)
        out["cohort_loss_gap"] = _rel(sum(il for _, il in imp),
                                      sum(rl for _, rl in ref))
        self.median = {"update_gap": float(np.median(upd)),
                       "loss_gap": float(np.median(loss))}

        # ---- the merge of round t -------------------------------------
        trained = {r: set(s) for r, s in cap.seeds.items()}
        # the draw depends on the order of the ids: the pool's own
        profile = scenario.profiles(cap.client_ids, self.traffic,
                                    self.study_seed)
        try:
            entries = merge_entries(res.rounds, t, self.traffic["tau"],
                                    trained, profile)
        except MergeRecordError as err:
            self.merge_error = str(err)
            out["merge_gap"] = float("inf")
            entries = None
        if entries:
            coeffs = R.staleness_coefficients(
                [(p, len(self.train[c])) for c, p in entries], t)
            rows = [cap.rows[p][c][0].row(cap.rows[p][c][1])
                    for c, p in entries]
            want = R.weighted_sum(rows, coeffs, R.F32)
            if program:
                got = ravel_pytree(g_next)[0]
            else:
                got = R.weighted_sum(rows, coeffs, self._impl(impl)[0])
            g_flat = ravel_pytree(g_t)[0]
            out["merge_gap"] = leaf_gap(unravel(got - g_flat),
                                        unravel(want - g_flat))
        elif entries is not None:
            same = all(bool(jnp.array_equal(a, b)) for a, b in zip(
                jax.tree_util.tree_leaves(g_next),
                jax.tree_util.tree_leaves(g_t)))
            out["merge_gap"] = 0.0 if same else float("inf")

        # ---- evaluation after round e ---------------------------------
        g_eval = (cap.params[e + 1] if e + 1 < n_rounds
                  else res.final_params)
        cid_of = {id(ds): cid for cid, ds in self.test.items()}
        per = []
        for ds, acc_p, loss_p in cap.evals:
            if id(ds) not in cid_of:
                raise RuntimeError("an evaluation ran on data that is no "
                                   "client's test set")
            acc_r, loss_r = self._evaluate("reference", g_eval, ds)
            if not program:
                acc_p, loss_p = self._evaluate(impl, g_eval, ds)
            per.append((acc_p, acc_r, loss_p, loss_r, len(ds.y)))
        # the study's reported accuracies: the round-e evaluation, and for
        # the last round also the final one, each over its own sample of
        # clients, all samples the same size
        reported = [res.rounds[e].accuracy]
        if e == n_rounds - 1:
            reported.append(res.final_accuracy)
        k, rest = divmod(len(per), len(reported))
        if k == 0 or rest:
            raise RuntimeError(f"{len(per)} evaluations after round {e} "
                               f"for {len(reported)} reports")
        acc_gap, loss_gap = [], []
        for j, acc_report in enumerate(reported):
            grp = per[j * k:(j + 1) * k]
            n = sum(g[4] for g in grp)
            mean = lambda i: sum(g[i] * g[4] for g in grp) / n  # noqa: E731
            got_acc = acc_report if program else mean(0)
            acc_gap.append(abs(got_acc - mean(1)))
            loss_gap.append(_rel(mean(2), mean(3)))
        out["eval_loss_gap"] = max(loss_gap)
        out["eval_acc_gap"] = max(acc_gap)
        return out
