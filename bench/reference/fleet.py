"""Plain reference of the fleet's communication rounds, and the comparison
that decides ``correct`` in a fleet cell.

It checks each recorded round from the state the round started in (the
bandit statistics and the merged update the program carried into it) and
from what the round was fed (the staged batches, masks and channel rates,
the adapter draws, the simulated devices and data sizes), with the base
weights drawn again from the seed. Per task and round it computes, as the
paper's method and the repository describe them:

1. the arm each vehicle in coverage pulls: UCB-DUAL's argmax of
   ``R̂ − λ·Ê + ε·sqrt(ln m / (n + 1))`` over its arms, an unexplored arm
   first (exploration, then exploitation once every arm has been tried);
2. the energy priced for the round (download, computation, upload, and the
   RSU's aggregation);
3. in the rounds whose training it replays, each vehicle's start and its
   local fine-tuning, and the aggregation, the data-size-weighted mean of
   the trained updates ``S0 · A B``. A vehicle starts from its recorded
   draw cut to its rank while the task has no merged update, and otherwise
   from the merged update redistributed: its truncated SVD (subspace
   iteration to convergence), ``A = U_r S_r / S0``, ``B = V_rᵀ``. Training is
   Adam on the adapter, ``counts`` steps of cross-entropy on the vehicle's
   own batches, the base frozen.

The program redistributes by a seeded randomized SVD, whose trailing
directions, like those of any SVD of an update with near-equal singular
values, rounding decides; Adam's normalised steps then carry those
directions into every entry of the trained adapters. So a redistributed
round is compared by the size of the change it made to each merged update,
not entry by entry (see PERF.md).

It imports nothing of the program and takes none of its weights.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as wgen
from bench.reference import transformer as ref

LR_B1, LR_B2, ADAM_EPS = 0.9, 0.999, 1e-8


def adapter_pairs(tree) -> Dict[str, Any]:
    """{target: (A, B)} from the program-layout adapter tree
    ``segments[0]/{attn,mlp}/<target>/{a,b}``."""
    return {name: (node["a"], node["b"])
            for nodes in tree["segments"][0].values()
            for name, node in nodes.items()}


def adapter_deltas(tree) -> Dict[str, Any]:
    """{target: delta} from the program-layout merged tree."""
    return {name: node["delta"] for nodes in tree["segments"][0].values()
            for name, node in nodes.items()}


def payload_params(model, rank: int) -> int:
    """Parameters of a rank-r adapter over every target of every layer."""
    L = ref.dims(model)["L"]
    return sum(rank * (di + do) * L for _, di, do in
               ref.targets(model).values())


def active_params(model) -> float:
    """Parameters the cost model counts per token, the base of the
    compute-cost factor g: the blocks' attention and MLP weights, the
    embedding and the output head."""
    g = ref.dims(model)
    d, f, H, Hkv, hd = g["d"], g["f"], g["H"], g["Hkv"], g["hd"]
    attn = d * hd * (H + 2 * Hkv) + H * hd * d
    mlp = d * f * (3 if ref.is_glu(model) else 2)
    embed = d * g["V"] * (1 if model.get("tie_embeddings") else 2)
    return float(g["L"] * (attn + mlp) + embed)


def select_arms(ucb: Dict[str, np.ndarray], epsilon: float,
                active: np.ndarray, dt=np.float64) -> np.ndarray:
    """UCB-DUAL's choice per vehicle from the bandit statistics the round
    starts with (``counts``, ``reward_sum``, ``energy_sum`` (V, K),
    ``lam``, ``round``); -1 for a vehicle out of coverage. The score is
    worked in ``dt``."""
    n = np.asarray(ucb["counts"], dt)
    m = max(float(ucb["round"]), 1.0)
    r_hat = np.asarray(ucb["reward_sum"], dt) / np.maximum(n, 1)
    e_hat = np.asarray(ucb["energy_sum"], dt) / np.maximum(n, 1)
    bonus = (dt(epsilon) * np.sqrt(dt(np.log(m)) / (n + 1))).astype(dt)
    score = (r_hat - dt(float(ucb["lam"])) * e_hat).astype(dt) + bonus
    score = np.where(n == 0, dt(1e9) + bonus, score)
    arms = np.argmax(score, axis=-1)
    return np.where(np.asarray(active, bool), arms, -1)


def _adam_lanes(W, pairs, scale, tokens, labels, counts, lr, *, model, dt,
                half_batch: bool):
    """Adam fine-tuning of every lane's adapter: pairs hold (V, L, ., .)
    factors, tokens (V, steps, B, S), counts (V,) live steps."""
    steps = tokens.shape[1]

    def lane(ad, sc, tok, lab, n):
        def loss(ad, t, l):
            if half_batch:       # a planted fault: half of each batch
                t, l = t[: t.shape[0] // 2], l[: l.shape[0] // 2]
            return ref.class_loss(W, model, ad, sc, t, l, dt)

        def step(state, xs):
            ad, m, v = state
            t, l, s = xs
            g = jax.grad(loss)(ad, t, l)
            k = (s + 1).astype(jnp.float32)
            m2 = jax.tree_util.tree_map(
                lambda m_, g_: LR_B1 * m_ + (1 - LR_B1) * g_, m, g)
            v2 = jax.tree_util.tree_map(
                lambda v_, g_: LR_B2 * v_ + (1 - LR_B2) * g_ * g_, v, g)
            bc1, bc2 = 1 - LR_B1 ** k, 1 - LR_B2 ** k
            ad2 = jax.tree_util.tree_map(
                lambda p, m_, v_: (p - lr * (m_ / bc1) / (
                    jnp.sqrt(v_ / bc2) + ADAM_EPS)).astype(p.dtype),
                ad, m2, v2)
            pick = lambda new, old: jax.tree_util.tree_map(
                lambda a, b: jnp.where(s < n, a, b), new, old)
            return (pick(ad2, ad), pick(m2, m), pick(v2, v)), None

        zeros = jax.tree_util.tree_map(jnp.zeros_like, ad)
        (ad, _, _), _ = jax.lax.scan(step, (ad, zeros, zeros),
                                     (tok, lab, jnp.arange(steps)))
        return ad

    return jax.vmap(lane)(pairs, scale, tokens, labels, counts)


@functools.partial(jax.jit, static_argnames=("p", "iters"))
def _leading_subspace(delta, p: int, iters: int):
    """An orthonormal basis Q (L, d_in, p) of the leading singular
    subspace of each layer's update, by subspace iteration from a fixed
    Gaussian start, and ``Qᵀ·delta`` (L, p, d_out)."""
    omega = jax.random.normal(jax.random.PRNGKey(0),
                              (delta.shape[-1], p), jnp.float32)
    q, _ = jnp.linalg.qr(delta @ omega)

    def step(_, q):
        z, _ = jnp.linalg.qr(jnp.swapaxes(delta, -1, -2) @ q)
        q, _ = jnp.linalg.qr(delta @ z)
        return q

    q = jax.lax.fori_loop(0, iters, step, q)
    return q, jnp.swapaxes(q, -1, -2) @ delta


def top_svd(delta, rmax: int):
    """Leading ``rmax`` singular triplets (U, s, Vᵀ) of each layer's update
    (L, d_in, d_out): subspace iteration on the device to ``rmax + 16``
    directions (eight rounds, so that the leading ``rmax`` have converged
    to rounding), then the SVD of the small projected matrix on the host
    in float64."""
    q, b = _leading_subspace(jnp.asarray(delta, jnp.float32), rmax + 16, 8)
    ub, s, vt = np.linalg.svd(np.asarray(b, np.float64),
                              full_matrices=False)
    u = np.asarray(q, np.float64) @ ub
    return (jnp.asarray(u[..., :rmax], jnp.float32),
            jnp.asarray(s[..., :rmax], jnp.float32),
            jnp.asarray(vt[..., :rmax, :], jnp.float32))


def merged_svd(rec: Dict[str, Any], t: int, rmax: int) -> Dict[str, Any]:
    """The truncated SVD of task t's merged update out of recorded round
    ``rec``, in float32 at the highest precision; worked once and kept in
    the round's record, since every replay starts from it."""
    done = rec.setdefault("svd", {})
    if t not in done:
        with jax.default_matmul_precision("highest"):
            done[t] = {k: top_svd(d, rmax) for k, d in adapter_deltas(
                rec["merged_out"][t]).items()}
    return done[t]


def redistribute(factors: Dict[str, Any], s0: float, mask: jnp.ndarray,
                 dt) -> Dict[str, Any]:
    """Each vehicle's start from the merged update's SVD factors:
    ``A = U_r S_r / S0``, ``B = V_rᵀ`` at its rank (columns and rows past
    it zero)."""
    out = {}
    for k, (u, s, vt) in factors.items():
        a = (u * s[..., None, :]) / s0
        out[k] = ((a[None] * mask[:, None, None, :]).astype(dt),
                  (vt[None] * mask[:, None, :, None]).astype(dt))
    return out


def replay(seed: int, model: Dict[str, Any], lora: Dict[str, Any],
           env: Dict[str, Any], recorded: List[Dict[str, Any]],
           dt=jnp.float32, half_batch: bool = False,
           precision: str = "highest") -> List[Dict[str, Any]]:
    """The reference's view of each recorded round, in the form of
    :func:`program_view`: per task the arms pulled (V,), the energy, and
    in the rounds whose training it replays the merged update
    ``{target: (L, d_in, d_out)}`` (None elsewhere). ``dt`` is the
    precision of the whole computation (a bfloat16 control);
    ``half_batch`` plants a fault."""
    W = wgen.draw_tree(ref.weight_shapes(model), seed, stream=0)
    cand = np.asarray(lora["candidate_ranks"])
    rmax = int(lora["max_rank"])
    alpha = float(lora["alpha"])
    s0 = alpha / float(lora["rank"])
    sim = env["sim"]
    steps_full = sim["local_steps"]
    ns_full = int(sim["batch_size"] * steps_full)
    ns_dep = int(sim["batch_size"] * steps_full * sim["departure_fraction"])
    g_base = 4.0 * active_params(env["cost_model"])
    T = env["data_sizes"].shape[0]
    np_dt = np.float64 if dt == jnp.float32 else jnp.bfloat16
    train = jax.jit(functools.partial(_adam_lanes, model=model, dt=dt,
                                      half_batch=half_batch))
    out = []
    with jax.default_matmul_precision(precision):
        for r_idx, rec in enumerate(recorded):
            x = rec["x"]
            view = {"arms": [], "energy": [], "merged": []}
            for t in range(T):
                act = np.asarray(x["active"][t], bool)
                arms = select_arms(rec["ucb_in"][t], env["ucb_epsilon"],
                                   act, np_dt)
                ranks = cand[np.maximum(arms, 0)]
                view["arms"].append(arms)
                view["energy"].append(_energy(
                    model, env, x, t, act, ranks, g_base, ns_full, ns_dep,
                    dt))
                merged = None
                if rec["trained"] and act.any():
                    start = (merged_svd(recorded[r_idx - 1], t, rmax)
                             if rec["has_merged_in"][t] else None)
                    merged = _train_round(W, train, start, rec["fresh"][t],
                                          ranks, rmax, alpha, s0, x, t, env,
                                          act, sim["lr"], dt)
                view["merged"].append(merged)
            out.append(view)
    return out


def _train_round(W, train, start, fresh_tree, ranks, rmax, alpha, s0, x, t,
                 env, act, lr, dt):
    """Task t's merged update after its vehicles train, each from its
    start cut to its rank: the recorded draws (``start`` None) or the
    merged update whose SVD factors ``start`` holds."""
    mask = jnp.asarray(np.arange(rmax)[None, :] < ranks[:, None], dt)
    if start is None:
        pairs = {k: (jnp.asarray(a, dt) * mask[:, None, None, :],
                     jnp.asarray(b, dt) * mask[:, None, :, None])
                 for k, (a, b) in adapter_pairs(fresh_tree).items()}
    else:
        pairs = redistribute(start, s0, mask, dt)
    scale = jnp.asarray(alpha / np.maximum(ranks, 1), dt)
    trained = train(W, pairs, scale, jnp.asarray(x["tokens"][t]),
                    jnp.asarray(x["labels"][t]), jnp.asarray(x["counts"][t]),
                    jnp.asarray(lr, dt))
    w = np.where(act, env["data_sizes"][t], 0.0)
    wn = jnp.asarray(w / max(w.sum(), 1e-12), dt)
    return {k: np.asarray((s0 * jnp.einsum("v,vlir,vlro->lio", wn, a, b)
                           ).astype(jnp.float32))
            for k, (a, b) in trained.items()}


def _energy(model, env, x, t, act, ranks, g_base, ns_full, ns_dep, dt):
    """Energy of task t's round: each vehicle in coverage pays download,
    computation and upload; the RSU pays aggregation per vehicle kept.
    In the bfloat16 control (``dt`` bfloat16) the arithmetic is rounded to
    bfloat16 at every step."""
    sim = env["sim"]
    rnd = (lambda a: np.asarray(jnp.asarray(a, dt).astype(jnp.float32),
                                np.float64)) if dt != jnp.float32 else (
        lambda a: np.asarray(a, np.float64))
    cost = env["cost_model"]
    payload = rnd([payload_params(cost, int(r)) for r in ranks])
    g = rnd(1.0 + 6.0 * payload / g_base)
    bits = rnd(payload * sim["bytes_per_param"] * 8.0)
    dep = np.asarray(x["departing"][t], bool)
    ns = np.where(dep, ns_dep, ns_full)
    rd = np.maximum(np.asarray(x["rate_down"][t], np.float64), 1e-9)
    ru = np.maximum(np.asarray(x["rate_up"][t], np.float64), 1e-9)
    tau_down = rnd(bits / rd)
    tau_comp = rnd(rnd(env["flops_per_sample"] * ns * g) / env["freq"])
    tau_up = rnd(bits / ru)
    e = rnd(env["rsu_tx_power"] * tau_down) + rnd(
        rnd(env["kappa"] * env["freq"] ** 3) * tau_comp) + rnd(
        env["tx_power"] * tau_up)
    agg_tau = env["rsu_agg_flops_per_vehicle"] / env["rsu_freq"]
    agg_e = env["rsu_kappa"] * env["rsu_freq"] ** 3 * agg_tau
    return float(np.sum(np.where(act, e, 0.0)) + agg_e * act.sum())


def program_view(recorded: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """The program's recorded rounds in the reference's form: the arm each
    vehicle pulled (the pull counts it added, -1 for none), the energy of
    each task, and the merged update of the rounds whose training the
    reference replays."""
    out = []
    for rec in recorded:
        arms = []
        for c_in, c_out in zip(rec["ucb_in"], rec["ucb_out"]):
            d = (np.asarray(c_out["counts"]) - np.asarray(c_in["counts"]))
            d = d[: np.asarray(rec["x"]["active"]).shape[1]]
            arms.append(np.where(d.sum(axis=1) > 0, np.argmax(d, axis=1),
                                 -1))
        out.append({"arms": arms, "energy": list(np.asarray(rec["energy"])),
                    "merged": [None if m is None else adapter_deltas(m)
                               for m in rec["merged_out"]]})
    return out


def _norms(merged, start) -> Dict[str, float]:
    return {k: float(np.linalg.norm(np.asarray(v, np.float64)
                                    - np.asarray(start[k], np.float64)))
            for k, v in merged.items()}


def compare(got: List[Dict[str, Any]], want: List[Dict[str, Any]],
            recorded: List[Dict[str, Any]]) -> Dict[str, float]:
    """The numbers that decide ``correct``, from two views of the same
    rounds (``got`` in the program's place):

    - ``rank_mismatch``: vehicles, over every task and round, whose arm
      differs (a pull where none is due counts too);
    - ``energy_rel``: the largest relative gap of a task's round energy;
    - ``merged_rel``: the largest ``‖M_got − M_want‖ / ‖M_want‖`` over the
      targets of every update trained from the recorded draws (entry by
      entry);
    - ``change_gap``: over the targets of every update trained from a
      redistributed one, the largest gap between the two norms of the
      change the round made, ``‖M_out − M_in‖``, against the reference's
      norm of that target or of the round's median target, whichever is
      larger.
    """
    mism = 0
    e_rel = m_rel = c_gap = 0.0
    for r_idx, (g, w, rec) in enumerate(zip(got, want, recorded)):
        changes = []
        for t in range(len(w["arms"])):
            mism += int(np.sum(np.asarray(g["arms"][t])
                               != np.asarray(w["arms"][t])))
            e_ref = w["energy"][t]
            e_rel = max(e_rel, abs(float(g["energy"][t]) - e_ref)
                        / max(abs(e_ref), 1e-30))
            ref_m = w["merged"][t]
            if ref_m is None:
                continue
            if not rec["has_merged_in"][t]:
                for k, v in ref_m.items():
                    p = np.asarray(g["merged"][t][k], np.float64)
                    v = np.asarray(v, np.float64)
                    m_rel = max(m_rel, float(np.linalg.norm(p - v)
                                             / max(np.linalg.norm(v),
                                                   1e-30)))
                continue
            start = adapter_deltas(recorded[r_idx - 1]["merged_out"][t])
            changes.append((_norms(g["merged"][t], start),
                            _norms(ref_m, start)))
        if changes:
            med = float(np.median([n for _, nr in changes
                                   for n in nr.values()]))
            for ng, nr in changes:
                for k, n in nr.items():
                    c_gap = max(c_gap, abs(ng[k] - n) / max(n, med, 1e-30))
    return {"rank_mismatch": float(mism), "energy_rel": e_rel,
            "merged_rel": m_rel, "change_gap": c_gap}


def unchanged(view: List[Dict[str, Any]], recorded: List[Dict[str, Any]]
              ) -> List[Dict[str, Any]]:
    """A planted fault: ``view`` with every trained round handing back the
    merged update it started from (none, where it trained from draws)."""
    out = []
    for r_idx, (v, rec) in enumerate(zip(view, recorded)):
        merged = []
        for t, m in enumerate(v["merged"]):
            if m is None:
                merged.append(None)
            elif rec["has_merged_in"][t]:
                merged.append(adapter_deltas(
                    recorded[r_idx - 1]["merged_out"][t]))
            else:
                merged.append({k: np.zeros_like(a) for k, a in m.items()})
        out.append(dict(v, merged=merged))
    return out


def exploit_decisions(recorded: List[Dict[str, Any]]) -> int:
    """Choices made by exploitation: vehicles in coverage that had tried
    every arm before the round."""
    n = 0
    for rec in recorded:
        for t, c_in in enumerate(rec["ucb_in"]):
            act = np.asarray(rec["x"]["active"][t], bool)
            tried = (np.asarray(c_in["counts"])[: act.shape[0]] > 0).all(1)
            n += int(np.sum(act & tried))
    return n


def check_rounds(seed, model, lora, env, recorded) -> Dict[str, float]:
    """The compared numbers for the program's recorded rounds."""
    return compare(program_view(recorded),
                   replay(seed, model, lora, env, recorded), recorded)
