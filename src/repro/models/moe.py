"""Mixture-of-Experts with sort-based dispatch + expert-parallel all_to_all.

This is the paper's §3.2 dynamic load balancing transplanted to token routing:
tokens are the walkers, experts are the processors, the
capacity factor realizes ``find_optimal_workload``'s balanced target, and the
``all_to_all`` exchange is ``redistribute_work`` on the ICI torus.  The
auxiliary balancing loss *drives the router towards the balanced distribution*
that the paper's rebalancer would impose after the fact — the differentiable
version of the same idea.

Dispatch is sort-based (argsort by expert, capacity-bounded scatter), NOT a
one-hot einsum: HLO FLOPs then consist of the true expert GEMMs only, keeping
`cost_analysis()` (and the roofline) honest.

The block is written in the paper's explicit-communication style inside a
``shard_map``; with ``rules=None``/``SerialComm`` the identical code runs on
one device (serial/parallel duality, as in the paper).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.comm import Comm, SerialComm
from repro.mesh.axes import AxisRules, logical_to_mesh
from repro.models.module import Param

# placement split fractions are q8 fixed-point: a dispatch map entry of
# ``split_q`` sends the first ``split_q * C // PLACE_Q`` capacity positions
# of an expert to its first physical slot and the rest to its second —
# integer math, so the split is deterministic at every capacity C
PLACE_Q = 256


def moe_def(cfg) -> dict:
    """The router keeps all ``n_experts`` outputs; the expert stacks hold
    the config's share (``cfg.held_experts`` of them)."""
    d, E, eff = cfg.d_model, cfg.n_experts, cfg.expert_d_ff
    H, lo = cfg.held_experts, cfg.first_held_expert
    if not (0 < H and 0 <= lo and lo + H <= E):
        raise ValueError(
            f"{cfg.name}: held experts {lo}..{lo + H - 1} are not a range of "
            f"the router's {E} experts")
    return {
        "router": Param((d, E), P("embed", None), init="small"),
        "gate": Param((H, d, eff), P("experts", "expert_embed", "expert_mlp")),
        "up": Param((H, d, eff), P("experts", "expert_embed", "expert_mlp")),
        "down": Param((H, eff, d), P("experts", "expert_mlp", "expert_embed")),
    }


def capacity(tokens_local: int, top_k: int, n_experts: int, cf: float) -> int:
    """Per-shard, per-expert slot budget — ``find_optimal_workload`` with
    uniform timings becomes the balanced ±1 split scaled by the capacity
    factor.  ``cf < 1`` deliberately under-provisions (tokens beyond the
    budget are dropped and counted); ``top_k > n_experts`` can never route
    and is refused outright."""
    if top_k > n_experts:
        raise ValueError(
            f"top_k={top_k} > n_experts={n_experts}: every token would need "
            "more distinct experts than exist")
    c = math.ceil(tokens_local * top_k / n_experts * cf)
    return max(4, ((c + 3) // 4) * 4)


def identity_placement(n_experts: int) -> np.ndarray:
    """The (3, E) int32 dispatch map that reproduces the unplaced layout
    (expert e in physical slot e, no replicas): rows are [slot_a, slot_b,
    split_q] — see ``placement`` in :func:`_dispatch_compute_combine`."""
    e = np.arange(n_experts, dtype=np.int32)
    return np.stack([e, e, np.zeros(n_experts, np.int32)])


def empty_expert_stats(n_experts: int) -> dict:
    z = jnp.zeros((n_experts,), jnp.int32)
    return {"tokens": z, "dropped": z}


def _dispatch_compute_combine(x2d, wr, wg, wu, wd, cfg, comm, tp_comm=None,
                              shard_comm=None, placement=None):
    """Core routed computation on one shard.  x2d: (T_l, d).

    ``tp_comm``: expert-TP mode — the expert ff dim is sharded over this
    axis; the down projection's partial sums are psum'd across it.

    ``shard_comm``: serving-TP mode (activations replicated, expert weights
    sharded over this axis).  Routing, capacity dropping and the combine all
    run replicated — identical to the serial path — and only the expert
    GEMMs are sharded: each rank computes its expert slice of the
    (replicated) dispatch buffer and one ``all_gather`` restores the full
    buffer, so each per-expert contraction happens on exactly one rank and
    the result is bitwise equal to the serial dispatch.

    ``placement``: (3, E) int32 device array [slot_a, slot_b, split_q] from
    ``serve.placement`` — logical expert e's first ``split_q[e] * C //
    PLACE_Q`` capacity positions go to physical slot ``slot_a[e]``, the rest
    to ``slot_b[e]``; a slot of -1 means the expert holds no weights (its
    tokens are dropped and counted).  The weight leaves must already be
    permuted to match (``placement.apply_placement``).  ``None`` and the
    identity map produce the exact integer slot indices of the unplaced
    path, so streams are bitwise unchanged.

    A config that holds a share of the experts (``cfg.held_experts`` from
    ``cfg.first_held_expert`` on; the weight stacks hold only those) routes
    over all ``n_experts`` and computes only the held experts' part of
    ``y``.  Assignments to experts it does not hold are the work of the
    chips that hold them: they are neither computed nor counted as
    dropped.  Such a share is dropless (``C = T_l``): it sees only its own
    chip's tokens, so a budget balanced over all experts has no meaning
    there, and since top-k picks distinct experts no expert can receive
    more than ``T_l`` assignments.  The dispatch buffer spans the held
    experts only, ``(held, C, d)``.

    Returns ``(y, aux, stats)`` with int32 per-logical-expert telemetry
    ``stats = {"tokens": routed assignments (top_k multiplicity), "dropped":
    assignments to held experts lost to capacity or eviction}`` — local to
    this shard's tokens (replicated = global in serving)."""
    T_l, d = x2d.shape
    E, k = cfg.n_experts, cfg.top_k
    H, lo = cfg.held_experts, cfg.first_held_expert
    ep = comm.size()
    assert H % ep == 0, (H, ep)
    E_loc = H // ep
    C = capacity(T_l, k, E, cfg.capacity_factor)
    if not cfg.holds_all_experts:
        C = T_l

    # --- route ------------------------------------------------------------
    logits = (x2d.astype(jnp.float32) @ wr.astype(jnp.float32))      # (T_l, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)                           # (T_l, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)  # renorm

    # --- aux losses (global means via psum) ---------------------------------
    me = jnp.mean(probs, axis=0)                                     # (E,)
    ce_frac = jnp.zeros((E,), jnp.float32).at[top_e.reshape(-1)].add(
        1.0 / (T_l * k))
    me = comm.all_reduce_sum(me) / max(comm.size(), 1)
    ce_frac = comm.all_reduce_sum(ce_frac) / max(comm.size(), 1)
    aux = E * jnp.sum(me * ce_frac)
    zl = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    aux = aux + cfg.router_z_weight * comm.all_reduce_sum(zl) / max(comm.size(), 1)

    # --- sort-based dispatch ------------------------------------------------
    flat_e = top_e.reshape(-1)                                       # (T_l*k,)
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    sorted_tok = order // k
    oh = jax.nn.one_hot(sorted_e, E, dtype=jnp.int32)
    pos_in_e = (jnp.cumsum(oh, axis=0) * oh).sum(-1) - 1             # rank in expert
    keep = pos_in_e < C
    if placement is None:
        slot_e, pos = sorted_e, pos_in_e
    else:
        slot_a, slot_b, split_q = placement[0], placement[1], placement[2]
        sp = (split_q[sorted_e] * C) // PLACE_Q          # per-assignment split
        use_b = pos_in_e >= sp
        slot_e = jnp.where(use_b, slot_b[sorted_e], slot_a[sorted_e])
        pos = jnp.where(use_b, pos_in_e - sp, pos_in_e)
        keep = keep & (slot_e >= 0)                      # evicted -> dropped
    keep = keep & (sorted_e >= lo) & (sorted_e < lo + H)  # held here
    slot = jnp.where(keep, (slot_e - lo) * C + pos, H * C)   # drop -> OOB
    buf = jnp.zeros((H * C + 1, d), x2d.dtype).at[slot].set(
        x2d[sorted_tok], mode="drop")
    buf = buf[:-1].reshape(H, C, d)

    # --- telemetry: per-logical-expert routed / dropped assignments ---------
    routed = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
    kept = jnp.zeros((E,), jnp.int32).at[sorted_e].add(keep.astype(jnp.int32))
    held = (jnp.arange(E) >= lo) & (jnp.arange(E) < lo + H)
    stats = {"tokens": routed, "dropped": jnp.where(held, routed - kept, 0)}

    # --- EP exchange: redistribute_work on the torus ------------------------
    buf = comm.all_to_all(buf, split_axis=0, concat_axis=1)          # (E_loc, C*ep, d)
    if shard_comm is not None:
        # serving TP: buf is replicated; take my expert rows only
        n_sh = shard_comm.size()
        assert wg.shape[0] * n_sh == E_loc, (wg.shape, n_sh, E_loc)
        buf = jax.lax.dynamic_slice_in_dim(
            buf, shard_comm.rank() * wg.shape[0], wg.shape[0], axis=0)

    # --- expert GEMMs (the only matmul FLOPs in the block) -------------------
    g = jnp.einsum("ecd,edf->ecf", buf, wg.astype(buf.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, wu.astype(buf.dtype))
    h = jax.nn.silu(g) * u
    out = jnp.einsum("ecf,efd->ecd", h, wd.astype(h.dtype))
    if tp_comm is not None:
        # expert-TP: ff dim sharded; sum the down-projection partials
        out = tp_comm.all_reduce_sum(out.astype(jnp.float32)).astype(out.dtype)
    if shard_comm is not None:
        # rank order == expert order, so the gather is the identity layout
        out = shard_comm.all_gather(out, axis=0, tiled=True)         # (E_loc, C, d)

    # --- return + combine ----------------------------------------------------
    out = comm.all_to_all(out, split_axis=1, concat_axis=0)          # (H, C, d)
    out = out.reshape(H * C, d)
    gathered = jnp.where(keep[:, None], out[jnp.clip(slot, 0, H * C - 1)], 0.0)
    w_sorted = top_p.reshape(-1)[order]
    contrib = gathered * w_sorted[:, None].astype(gathered.dtype)
    y = jnp.zeros((T_l, d), x2d.dtype).at[sorted_tok].add(contrib)
    return y, aux, stats


def moe_apply_expert_parallel(params, x, cfg, ep_comm: Comm,
                              shard_comm: Comm | None = None, placement=None):
    """MoE block with experts PARTITIONED over ``ep_comm``'s mesh axis.

    Serving-mode expert parallelism: activations (and therefore routing,
    the capacity drop rule and the combine) are replicated over every mesh
    axis; the (E, C, d) dispatch buffer is exchanged through
    ``ep_comm.all_to_all`` so each rank holds the capacity rows of its own
    E/ep experts, runs the expert GEMMs against its local expert weights,
    and the reverse ``all_to_all`` hands every rank back the full combined
    buffer.  Because the buffer is replicated before the exchange, each
    per-expert contraction happens with bit-identical inputs and weights to
    the serial path, on exactly one rank — so greedy token streams are
    bitwise equal to ``SerialComm`` / tp=1.

    Composes with Megatron serving TP as a 2-D ``(expert, model)`` mesh:
    pass the model-axis ``Comm`` as ``shard_comm`` and the per-rank expert
    weights arrive (E/(ep*tp), ...).  ``SerialComm()`` as ``ep_comm``
    recovers the single-device / pure-TP path.

    Returns ``(y, aux, stats)`` — see :func:`_dispatch_compute_combine` for
    ``placement`` and the telemetry dict.
    """
    y2d, aux, stats = _dispatch_compute_combine(
        x.reshape(-1, x.shape[-1]), params["router"], params["gate"],
        params["up"], params["down"], cfg, ep_comm,
        shard_comm=shard_comm, placement=placement)
    return y2d.reshape(x.shape), aux, stats


def moe_apply_serve_tp(params, x, cfg, shard_comm: Comm, placement=None):
    """MoE block INSIDE a serving-TP ``shard_map`` body.

    Activations are replicated over the ``model`` axis and the expert
    weights arrive expert-sharded (``gate``/``up``/``down``: (E/tp, ...) per
    rank; ``router`` replicated).  Routing and the capacity-bounded dispatch
    replicate the serial ``moe_apply`` math exactly; only the expert GEMMs
    run sharded (see ``shard_comm`` in :func:`_dispatch_compute_combine`),
    which keeps greedy token streams bit-identical to the tp=1 engine while
    cutting per-rank expert FLOPs by tp.  Returns ``(y, aux, stats)``.
    """
    return moe_apply_expert_parallel(params, x, cfg, SerialComm(),
                                     shard_comm=shard_comm,
                                     placement=placement)


def moe_apply(params, x, cfg, rules: AxisRules | None):
    """x: (B, S, d) -> (y, aux_loss)."""
    wr, wg, wu, wd = (params["router"], params["gate"], params["up"],
                      params["down"])

    if rules is None or rules.mesh is None:
        y2d, aux, _ = _dispatch_compute_combine(
            x.reshape(-1, x.shape[-1]), wr, wg, wu, wd, cfg, SerialComm())
        return y2d.reshape(x.shape), aux

    mesh = rules.mesh
    x_spec = logical_to_mesh(P("batch", "seq", None), rules)
    w_specs = {
        "router": logical_to_mesh(P("embed", None), rules),
        "gate": logical_to_mesh(P("experts", "expert_embed", "expert_mlp"),
                                rules),
        "up": logical_to_mesh(P("experts", "expert_embed", "expert_mlp"),
                              rules),
        "down": logical_to_mesh(P("experts", "expert_mlp", "expert_embed"),
                                rules),
    }
    fsdp_axes = rules.get("expert_embed")
    tp_axes = rules.get("expert_mlp")

    def _fsdp_gather(fs, w, dim):
        """All-gather a weight's FSDP-sharded ``dim`` (explicit ZeRO-3)."""
        g = fs.all_gather(w, tiled=False)             # (F, ...)
        g = jnp.moveaxis(g, 0, dim)                   # (..., F, d/F, ...)
        shape = list(w.shape)
        shape[dim] = -1
        return g.reshape(shape)

    def body(x_l, wr_l, wg_l, wu_l, wd_l):
        comm_ep = Comm("model")
        B_l, S_l, d = x_l.shape
        x2d = x_l.reshape(-1, d)
        if fsdp_axes is not None:
            # TRAIN mode (ZeRO-3): many tokens amortize a per-layer weight
            # gather; expert weights arrive d-sharded and are gathered.
            fs = Comm(fsdp_axes)
            wg_l = _fsdp_gather(fs, wg_l, 1)          # (E_loc, d, eff)
            wu_l = _fsdp_gather(fs, wu_l, 1)
            wd_l = _fsdp_gather(fs, wd_l, 2)          # (E_loc, eff, d)
            y, aux, _ = _dispatch_compute_combine(
                x2d, wr_l, wg_l, wu_l, wd_l, cfg, comm_ep)
        elif tp_axes is not None:
            # DECODE mode (weight-stationary expert TP): the token batch is
            # tiny, the weights are 480B — so move the tokens, never the
            # weights.  Gather this axis's few tokens, compute against the
            # local ff slice, psum the down partials, slice my rows back.
            tpc = Comm(tp_axes)
            T_l = x2d.shape[0]
            x_all = tpc.all_gather(x2d, tiled=True)   # (T_l * n_tp, d)
            y_all, aux, _ = _dispatch_compute_combine(
                x_all, wr_l, wg_l, wu_l, wd_l, cfg, comm_ep, tp_comm=tpc)
            y = jax.lax.dynamic_slice_in_dim(y_all, tpc.rank() * T_l, T_l, 0)
        else:
            y, aux, _ = _dispatch_compute_combine(
                x2d, wr_l, wg_l, wu_l, wd_l, cfg, comm_ep)
        aux = Comm(mesh.axis_names).all_reduce_sum(aux) / mesh.size
        return y.reshape(B_l, S_l, d), aux

    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, w_specs["router"], w_specs["gate"], w_specs["up"],
                  w_specs["down"]),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, wr, wg, wu, wd)
    return y, aux
