"""Cell matrix: every (architecture x input shape) combination as a
lowerable unit — step function + input pytree (ShapeDtypeStructs for the
dry-run, concrete arrays for smoke/examples) + logical shardings.

A *cell* is what the multi-pod dry-run lowers and compiles, what the
roofline harness analyses, and what the smoke tests execute at reduced
scale.  40 assigned cells + 2 spade cells (the paper's own workload).
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_FAMILY, Skip, arch_shapes, get_config, get_smoke_config
from repro.configs.base import GNNConfig, LMConfig, RecsysConfig, ShapeSpec, SpadeConfig
from repro.core.incremental import DeviceSpadeState, insert_and_maintain
from repro.core.peel import bulk_peel
from repro.graphstore.structs import DeviceGraph
from repro.models.gnn import GraphBatch, gnn_loss, init_gnn_params, make_triplets
from repro.models.transformer import (
    KVCache,
    cache_window,
    decode_step,
    init_lm_params,
    lm_loss,
    prefill,
)
from repro.models.two_tower import (
    RecsysBatch,
    init_two_tower_params,
    retrieval_scores,
    score_pairs,
    two_tower_loss,
)
from repro.train.optimizer import AdamConfig, TrainState, init_train_state
from repro.train.train_step import make_train_step

__all__ = ["Cell", "build_cell", "MODEL_AXIS"]

MODEL_AXIS = 16  # 'model' mesh axis size in the production meshes

_f32, _bf16, _i32, _b = jnp.float32, jnp.bfloat16, jnp.int32, jnp.bool_


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(int(s) for s in shape), dtype)


def _round_up(x: int, m: int = 512) -> int:
    """Shardable dims are padded to multiples of 512 (covers every mesh
    axis combination: pod*data=32, data*model=256); validity masks make
    padding semantically inert."""
    return -(-int(x) // m) * m


@dataclass
class Cell:
    arch: str
    shape: str
    family: str
    step_name: str
    fn: Callable  # fn(*args)
    args: tuple  # pytree of ShapeDtypeStruct (or concrete arrays)
    in_logical: tuple  # matching pytree of logical-axis tuples
    out_logical: Any  # logical axes for outputs (or None -> unspecified)
    donate: tuple[int, ...] = ()
    model_flops: float = 0.0  # analytic "useful" FLOPs for §Roofline


# ---------------------------------------------------------------------------
# logical-axis rule trees
# ---------------------------------------------------------------------------


def lm_param_logical(cfg: LMConfig, fsdp: bool = True) -> dict:
    F = "fsdp" if fsdp else None
    layers: dict[str, Any] = {
        "attn_norm": (None, None),
        "mlp_norm": (None, None),
        "wq": (None, F, "model"),
        "wk": (None, F, "model"),
        "wv": (None, F, "model"),
        "wo": (None, "model", F),
    }
    if cfg.qk_norm:
        layers["q_norm"] = (None, None)
        layers["k_norm"] = (None, None)
    if cfg.moe:
        if cfg.moe.expert_parallel:
            layers["moe"] = {
                "router": (None, F, None),
                "w_gate": (None, "expert", F, None),
                "w_up": (None, "expert", F, None),
                "w_down": (None, "expert", None, F),
            }
        else:
            layers["moe"] = {
                "router": (None, F, None),
                "w_gate": (None, None, F, "model"),
                "w_up": (None, None, F, "model"),
                "w_down": (None, None, "model", F),
            }
    else:
        layers["mlp"] = {
            "w_gate": (None, F, "model"),
            "w_up": (None, F, "model"),
            "w_down": (None, "model", F),
        }
    return {
        "embed": ("model", F),
        "layers": layers,
        "final_norm": (None,),
        "head": (F, "model"),
    }


def _state_logical(param_logical) -> TrainState:
    return TrainState(
        params=param_logical,
        m=param_logical,
        v=param_logical,
        step=(),
        err=None,
    )


def gnn_param_logical(params) -> Any:
    # GNN params are small: replicated
    return jax.tree.map(lambda p: tuple(None for _ in p.shape), params)


def recsys_param_logical() -> dict:
    rep2 = (None, None)
    mlp = lambda n: {f"w{i}": rep2 for i in range(n)} | {f"b{i}": (None,) for i in range(n)}
    return {
        "user_table": ("rows", None),
        "item_table": ("rows", None),
        "user_mlp": mlp(3),
        "item_mlp": mlp(3),
        "temp": (),
    }


# ---------------------------------------------------------------------------
# LM cells
# ---------------------------------------------------------------------------


def _lm_train_cell(arch, cfg: LMConfig, spec: ShapeSpec, concrete, rng,
                   roofline: bool = False) -> Cell:
    B, S = spec.global_batch, spec.seq_len
    adam = AdamConfig()
    loss = lambda params, batch: lm_loss(params, batch["tokens"], batch["labels"], cfg)
    # microbatched grad accumulation: 8x smaller live activations, and XLA
    # overlaps microbatch k's collectives with k+1's compute.  The roofline
    # variant uses microbatches=1 (identical total FLOPs, no scan).
    micro = 1 if roofline else (8 if B >= 64 else 1)
    step = make_train_step(loss, adam, microbatches=micro)

    def init_fn():
        return init_train_state(init_lm_params(jax.random.PRNGKey(0), cfg))

    if concrete:
        state = init_fn()
        tokens = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), _i32)
        batch = {"tokens": tokens, "labels": tokens}
    else:
        state = jax.eval_shape(init_fn)
        batch = {"tokens": _sds((B, S), _i32), "labels": _sds((B, S), _i32)}

    pl = lm_param_logical(cfg, fsdp=True)
    in_logical = (_state_logical(pl), {"tokens": ("batch", None), "labels": ("batch", None)})
    # 6ND (dense) / 6*N_active*D (MoE) + causal attention term
    n_act = cfg.n_active_params
    attn_flops = 2 * 3 * cfg.n_layers * B * S * S // 2 * cfg.n_heads * cfg.d_head
    mf = 6 * n_act * B * S + attn_flops
    return Cell(arch, spec.name, "lm", "train_step", step, (state, batch), in_logical,
                (_state_logical(pl), None), donate=(0,), model_flops=mf)


def _lm_prefill_cell(arch, cfg: LMConfig, spec: ShapeSpec, concrete, rng) -> Cell:
    B, S = spec.global_batch, spec.seq_len
    fn = functools.partial(prefill, cfg=cfg)
    if concrete:
        params = init_lm_params(jax.random.PRNGKey(0), cfg)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), _i32)
    else:
        params = jax.eval_shape(lambda: init_lm_params(jax.random.PRNGKey(0), cfg))
        tokens = _sds((B, S), _i32)
    pl = lm_param_logical(cfg, fsdp=False)
    cache_logical = KVCache(
        k=(None, "batch", "model", None, None), v=(None, "batch", "model", None, None)
    )
    mf = 2 * cfg.n_active_params * B * S + 2 * 2 * cfg.n_layers * B * S * S // 2 * cfg.n_heads * cfg.d_head
    return Cell(arch, spec.name, "lm", "prefill", fn, (params, tokens),
                (pl, ("batch", None)), (("batch", "model"), cache_logical),
                model_flops=mf)


def _lm_decode_cell(arch, cfg: LMConfig, spec: ShapeSpec, concrete, rng) -> Cell:
    B, S = spec.global_batch, spec.seq_len
    W, _ = cache_window(cfg, S)
    L, Hkv, Dh = cfg.n_layers, cfg.n_kv_heads, cfg.d_head
    fn = functools.partial(decode_step, cfg=cfg)
    dt = jnp.dtype(cfg.dtype)
    if concrete:
        params = init_lm_params(jax.random.PRNGKey(0), cfg)
        cache = KVCache(
            k=jnp.zeros((L, B, W, Hkv, Dh), dt), v=jnp.zeros((L, B, W, Hkv, Dh), dt)
        )
        token = jnp.asarray(rng.integers(0, cfg.vocab, (B,)), _i32)
        pos = jnp.full((B,), min(S - 1, W + 3), _i32)
    else:
        params = jax.eval_shape(lambda: init_lm_params(jax.random.PRNGKey(0), cfg))
        cache = KVCache(k=_sds((L, B, W, Hkv, Dh), dt), v=_sds((L, B, W, Hkv, Dh), dt))
        token = _sds((B,), _i32)
        pos = _sds((B,), _i32)
    pl = lm_param_logical(cfg, fsdp=False)
    b_ax = "batch" if B % 32 == 0 else None
    # GQA kv-heads (8) don't divide the model axis (16): shard the cache's
    # sequence dim instead (flash-decode style) — softmax over W becomes a
    # partial-reduce + all-reduce, which GSPMD emits automatically.
    cl = KVCache(k=(None, b_ax, "model", None, None), v=(None, b_ax, "model", None, None))
    mf = 2 * cfg.n_active_params * B + 2 * 2 * L * B * W * cfg.n_heads * Dh
    return Cell(arch, spec.name, "lm", "decode_step", fn, (params, cache, token, pos),
                (pl, cl, (b_ax,), (b_ax,)), ((b_ax, "model"), cl),
                donate=(1,), model_flops=mf)


# ---------------------------------------------------------------------------
# GNN cells
# ---------------------------------------------------------------------------


def _graph_batch(cfg: GNNConfig, spec: ShapeSpec, concrete, rng) -> tuple[GraphBatch, int]:
    """Build the fixed-shape GraphBatch for a shape spec."""
    if spec.kind == "graph_mini":
        # sampled block caps: seeds + fanout-expansion worst case
        seeds = spec.batch_nodes
        e1 = seeds * spec.fanout[0]
        e2 = e1 * spec.fanout[1] if len(spec.fanout) > 1 else 0
        E = e1 + e2
        N = seeds + E  # every sampled edge can introduce a new node
    elif spec.kind == "graph_batch":
        N = spec.n_nodes * spec.n_graphs
        E = spec.n_edges * spec.n_graphs
    else:
        N, E = spec.n_nodes, spec.n_edges
    N, E = _round_up(N), _round_up(E)
    F = spec.d_feat if spec.d_feat else cfg.d_feat
    T = E * cfg.triplet_cap_per_edge if cfg.kind == "dimenet" else 512
    Fe = 4 if cfg.kind == "meshgraphnet" else 0

    if not concrete:
        g = GraphBatch(
            node_feat=_sds((N, F), _f32),
            edge_src=_sds((E,), _i32),
            edge_dst=_sds((E,), _i32),
            edge_mask=_sds((E,), _b),
            node_mask=_sds((N,), _b),
            edge_feat=_sds((E, Fe), _f32),
            labels=_sds((N,), _i32),
            tri_in=_sds((T,), _i32),
            tri_out=_sds((T,), _i32),
            tri_angle=_sds((T,), _f32),
            tri_mask=_sds((T,), _b),
            edge_len=_sds((E,), _f32),
        )
        return g, F

    src = rng.integers(0, N, E).astype(np.int32)
    dst = rng.integers(0, N, E).astype(np.int32)
    if cfg.kind == "dimenet":
        ti, to, tm = make_triplets(src, dst, cfg.triplet_cap_per_edge, rng)
    else:
        ti = to = np.zeros(1, np.int32)
        tm = np.zeros(1, bool)
    g = GraphBatch(
        node_feat=jnp.asarray(rng.normal(size=(N, F)).astype(np.float32)),
        edge_src=jnp.asarray(src),
        edge_dst=jnp.asarray(dst),
        edge_mask=jnp.ones(E, bool),
        node_mask=jnp.ones(N, bool),
        edge_feat=jnp.asarray(rng.normal(size=(E, Fe)).astype(np.float32)),
        labels=jnp.asarray(rng.integers(0, cfg.n_classes, N).astype(np.int32)),
        tri_in=jnp.asarray(ti),
        tri_out=jnp.asarray(to),
        tri_angle=jnp.asarray(
            rng.uniform(0, np.pi, ti.shape[0]).astype(np.float32)
        ),
        tri_mask=jnp.asarray(tm),
        edge_len=jnp.asarray(rng.uniform(0.5, 4.0, E).astype(np.float32)),
    )
    return g, F


def _gnn_graph_logical(g: GraphBatch) -> GraphBatch:
    return GraphBatch(
        node_feat=("vertex", None),
        edge_src=("edges",),
        edge_dst=("edges",),
        edge_mask=("edges",),
        node_mask=("vertex",),
        edge_feat=("edges", None),
        labels=("vertex",),
        tri_in=("edges",),
        tri_out=("edges",),
        tri_angle=("edges",),
        tri_mask=("edges",),
        edge_len=("edges",),
    )


def _gnn_train_cell(arch, cfg: GNNConfig, spec: ShapeSpec, concrete, rng) -> Cell:
    g, F = _graph_batch(cfg, spec, concrete, rng)
    adam = AdamConfig(weight_decay=0.0)
    loss = lambda params, batch: gnn_loss(params, batch, cfg)
    step = make_train_step(loss, adam)

    def init_fn():
        return init_train_state(init_gnn_params(jax.random.PRNGKey(0), cfg, F))

    state = init_fn() if concrete else jax.eval_shape(init_fn)
    params_shapes = jax.eval_shape(lambda: init_gnn_params(jax.random.PRNGKey(0), cfg, F))
    pl = gnn_param_logical(params_shapes)
    in_logical = (_state_logical(pl), _gnn_graph_logical(g))
    E = g.edge_src.shape[0]
    N = g.node_feat.shape[0]
    mf = _gnn_model_flops(cfg, N, E, F) * 3.0  # fwd + bwd(2x)
    return Cell(arch, spec.name, "gnn", "train_step", step, (state, g), in_logical,
                (_state_logical(pl), None), donate=(0,), model_flops=float(mf))


def _gnn_model_flops(cfg: GNNConfig, N: int, E: int, F: int) -> float:
    """Analytic forward FLOPs (matmul-dominated terms; 2 flops/MAC)."""
    H, L, C = cfg.d_hidden, cfg.n_layers, cfg.n_classes
    if cfg.kind == "gcn":
        dims = [F] + [H] * (L - 1) + [C]
        fl = sum(2 * N * a * b + 4 * E * b for a, b in zip(dims[:-1], dims[1:]))
        return float(fl)
    if cfg.kind == "gat":
        hds = cfg.n_heads
        fl = 0
        d_in = F
        for li in range(L):
            d_out = C if li == L - 1 else H
            fl += 2 * N * d_in * hds * d_out  # projection
            fl += 6 * E * hds * d_out  # scores + weighted messages
            d_in = d_out if li == L - 1 else hds * d_out
        return float(fl)
    if cfg.kind == "meshgraphnet":
        n_mlp = cfg.mlp_layers
        enc = 2 * N * (F * H + (n_mlp - 1) * H * H) + 2 * E * (4 * H + (n_mlp - 1) * H * H)
        per_step = 2 * E * (3 * H * H + (n_mlp - 1) * H * H) + 2 * N * (
            2 * H * H + (n_mlp - 1) * H * H
        )
        dec = 2 * N * (H * H * (n_mlp - 1) + H * C)
        return float(enc + L * per_step + dec)
    # dimenet
    T = E * cfg.triplet_cap_per_edge
    B_, ns, nr, nb = L, cfg.n_spherical, cfg.n_radial, cfg.n_bilinear
    per_block = (
        2 * T * (ns * nr) * nb  # sbf basis projection
        + 2 * T * nb * H * H  # bilinear interaction
        + 2 * T * H  # msg gather mult
        + 2 * E * H * H * 3  # msg/out transforms
    )
    embed = 2 * N * F * H + 2 * E * nr * H
    return float(embed + B_ * per_block)


# ---------------------------------------------------------------------------
# recsys cells
# ---------------------------------------------------------------------------


def _recsys_batch(cfg: RecsysConfig, B, concrete, rng) -> RecsysBatch:
    Fu, Fi, M = cfg.n_user_fields, cfg.n_item_fields, cfg.multi_hot
    if not concrete:
        return RecsysBatch(
            user_idx=_sds((B, Fu, M), _i32),
            user_wt=_sds((B, Fu, M), _f32),
            item_idx=_sds((B, Fi, M), _i32),
            item_wt=_sds((B, Fi, M), _f32),
            log_q=_sds((B,), _f32),
        )
    return RecsysBatch(
        user_idx=jnp.asarray(rng.integers(0, cfg.user_vocab, (B, Fu, M)), _i32),
        user_wt=jnp.ones((B, Fu, M), _f32),
        item_idx=jnp.asarray(rng.integers(0, cfg.item_vocab, (B, Fi, M)), _i32),
        item_wt=jnp.ones((B, Fi, M), _f32),
        log_q=jnp.zeros((B,), _f32),
    )


_RB_LOGICAL = RecsysBatch(
    user_idx=("batch", None, None),
    user_wt=("batch", None, None),
    item_idx=("batch", None, None),
    item_wt=("batch", None, None),
    log_q=("batch",),
)


def _recsys_cell(arch, cfg: RecsysConfig, spec: ShapeSpec, concrete, rng) -> Cell:
    pl = recsys_param_logical()

    def init_fn():
        return init_two_tower_params(jax.random.PRNGKey(0), cfg)

    if spec.kind == "recsys_train":
        adam = AdamConfig(weight_decay=0.0)
        loss = lambda params, batch: two_tower_loss(params, batch, cfg)
        step = make_train_step(loss, adam)
        if concrete:
            state = init_train_state(init_fn())
        else:
            state = jax.eval_shape(lambda: init_train_state(init_fn()))
        batch = _recsys_batch(cfg, spec.batch, concrete, rng)
        B = spec.batch
        mf = (_recsys_flops(cfg, B) + 2.0 * B * B * cfg.tower_mlp[-1]) * 3
        return Cell(arch, spec.name, "recsys", "train_step", step, (state, batch),
                    (_state_logical(pl), _RB_LOGICAL), (_state_logical(pl), None),
                    donate=(0,), model_flops=mf)
    if spec.kind == "recsys_serve":
        fn = functools.partial(score_pairs, cfg=cfg)
        params = init_fn() if concrete else jax.eval_shape(init_fn)
        batch = _recsys_batch(cfg, spec.batch, concrete, rng)
        return Cell(arch, spec.name, "recsys", "score_pairs", fn, (params, batch),
                    (pl, _RB_LOGICAL), ("batch",), model_flops=_recsys_flops(cfg, spec.batch))
    # retrieval: one query vs n_candidates precomputed item embeddings
    fn = functools.partial(retrieval_scores, cfg=cfg, top_k=100)
    params = init_fn() if concrete else jax.eval_shape(init_fn)
    Fu, M, D = cfg.n_user_fields, cfg.multi_hot, cfg.embed_dim
    N = _round_up(spec.n_candidates)
    if concrete:
        uidx = jnp.asarray(rng.integers(0, cfg.user_vocab, (1, Fu, M)), _i32)
        uwt = jnp.ones((1, Fu, M), _f32)
        cand = jnp.asarray(rng.normal(size=(N, D)).astype(np.float32))
    else:
        uidx, uwt = _sds((1, Fu, M), _i32), _sds((1, Fu, M), _f32)
        cand = _sds((N, D), _f32)
    mf = 2.0 * N * D
    return Cell(arch, spec.name, "recsys", "retrieval", fn,
                (params, uidx, uwt, cand),
                (pl, (None, None, None), (None, None, None), ("rows", None)),
                None, model_flops=mf)


def _recsys_flops(cfg: RecsysConfig, B: int) -> float:
    D = cfg.embed_dim
    lookups = (cfg.n_user_fields + cfg.n_item_fields) * cfg.multi_hot * D
    dims_u = [cfg.n_user_fields * D, *cfg.tower_mlp]
    dims_i = [cfg.n_item_fields * D, *cfg.tower_mlp]
    mlp = sum(a * b for a, b in zip(dims_u[:-1], dims_u[1:])) + sum(
        a * b for a, b in zip(dims_i[:-1], dims_i[1:])
    )
    return float(B) * (2.0 * mlp + lookups)


# ---------------------------------------------------------------------------
# spade cells (the paper's own workload)
# ---------------------------------------------------------------------------


def _spade_graph(cfg: SpadeConfig, concrete, rng, n=None, e=None) -> DeviceGraph:
    N = _round_up(n or cfg.n_capacity)
    E = _round_up(e or cfg.e_capacity)
    if not concrete:
        return DeviceGraph(
            src=_sds((E,), _i32), dst=_sds((E,), _i32), c=_sds((E,), _f32),
            edge_mask=_sds((E,), _b), a=_sds((N,), _f32), vertex_mask=_sds((N,), _b),
            n_capacity=N, e_capacity=E,
        )
    from repro.graphstore.structs import device_graph_from_coo

    m = int(E * 0.9)
    src = rng.integers(0, N, m)
    dst = rng.integers(0, N, m)
    keep = src != dst
    return device_graph_from_coo(
        N, src[keep], dst[keep], np.ones(keep.sum(), np.float32),
        n_capacity=N, e_capacity=E,
    )


_DG_LOGICAL = dict(
    src=("edges",), dst=("edges",), c=("edges",), edge_mask=("edges",),
    a=(None,), vertex_mask=(None,),
)


def _spade_cells(arch, cfg: SpadeConfig, spec: ShapeSpec, concrete, rng) -> Cell:
    Ncap, Ecap = _round_up(cfg.n_capacity), _round_up(cfg.e_capacity)
    gl = DeviceGraph(
        n_capacity=Ncap, e_capacity=Ecap, **{k: v for k, v in _DG_LOGICAL.items()}
    )
    # essential per-round work: 2 segment-sum adds + 2 mask mults per edge,
    # plus threshold compare/update over vertices; an upper bound, since
    # the staged rounds stream fewer slots than the capacity (round_slots)
    E, R = Ecap, cfg.max_rounds
    mf = float(R) * (6.0 * E + 4.0 * Ncap)
    if spec.kind == "spade_static":
        fn = functools.partial(bulk_peel, eps=cfg.eps, max_rounds=cfg.max_rounds)
        g = _spade_graph(cfg, concrete, rng)
        return Cell(arch, spec.name, "spade", "bulk_peel", fn, (g,),
                    (gl,), None, model_flops=mf)
    # streaming maintenance cell
    fn = functools.partial(insert_and_maintain, eps=cfg.eps, max_rounds=cfg.max_rounds)
    B = cfg.batch_edges
    if concrete:
        g = _spade_graph(cfg, True, rng)
        from repro.core.incremental import init_state

        state = init_state(g, eps=cfg.eps)
        bs = jnp.asarray(rng.integers(0, g.n_capacity, B), _i32)
        bd = jnp.asarray(rng.integers(0, g.n_capacity, B), _i32)
        bc = jnp.ones((B,), _f32)
        valid = bs != bd
    else:
        g = _spade_graph(cfg, False, rng)
        state = DeviceSpadeState(
            graph=g, level=_sds((g.n_capacity,), _i32), best_g=_sds((), _f32),
            community=_sds((g.n_capacity,), _b), edge_count=_sds((), _i32),
            w0=_sds((g.n_capacity,), _f32),
        )
        bs = bd = _sds((B,), _i32)
        bc = _sds((B,), _f32)
        valid = _sds((B,), _b)
    sl = DeviceSpadeState(graph=gl, level=(None,), best_g=(), community=(None,),
                          edge_count=(), w0=(None,))
    return Cell(arch, spec.name, "spade", "insert_and_maintain", fn,
                (state, bs, bd, bc, valid),
                (sl, (None,), (None,), (None,), (None,)), sl,
                donate=(0,), model_flops=mf)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------


def build_cell(arch: str, shape: str, *, concrete: bool = False, smoke: bool = False,
               roofline: bool = False, override_layers: int | None = None,
               seed: int = 0) -> Cell | Skip:
    """Build one cell.  ``smoke=True`` swaps in the reduced config and
    shrinks the shape spec to CPU scale (same code path, tiny sizes).

    ``roofline=True`` builds the *analysis* variant: scans python-unrolled
    (XLA cost_analysis counts while bodies once — DESIGN.md §7), coarse
    attention blocks to bound HLO size, microbatches=1 (identical total
    FLOPs).  Never executed; memory numbers come from the production
    variant.  Spade cells have no analysis variant: their staged peel
    rounds are while loops whatever the flag."""
    fam = ARCH_FAMILY[arch]
    spec = arch_shapes(arch)[shape]
    if isinstance(spec, Skip):
        return spec
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    if smoke:
        spec = _shrink(spec)
    if roofline:
        if fam == "lm":
            qb = max(spec.seq_len // 4, 128) if spec.seq_len else 512
            cfg = dataclasses.replace(cfg, unroll=True, q_block=qb, kv_block=qb)
        elif fam == "gnn":
            cfg = dataclasses.replace(cfg, unroll=True)
    if override_layers is not None and hasattr(cfg, "n_layers"):
        cfg = dataclasses.replace(cfg, n_layers=override_layers)
    rng = np.random.default_rng(seed)
    if fam == "lm":
        if spec.kind == "train":
            return _lm_train_cell(arch, cfg, spec, concrete, rng, roofline=roofline)
        if spec.kind == "prefill":
            return _lm_prefill_cell(arch, cfg, spec, concrete, rng)
        return _lm_decode_cell(arch, cfg, spec, concrete, rng)
    if fam == "gnn":
        return _gnn_train_cell(arch, cfg, spec, concrete, rng)
    if fam == "recsys":
        return _recsys_cell(arch, cfg, spec, concrete, rng)
    if fam == "spade":
        return _spade_cells(arch, cfg, spec, concrete, rng)
    raise KeyError(arch)


def _shrink(spec: ShapeSpec) -> ShapeSpec:
    """CPU-scale version of a shape spec (same kind, tiny sizes)."""
    reps = {}
    if spec.seq_len:
        reps["seq_len"] = min(spec.seq_len, 64)
    if spec.global_batch:
        reps["global_batch"] = min(spec.global_batch, 2)
    if spec.n_nodes:
        reps["n_nodes"] = min(spec.n_nodes, 64)
    if spec.n_edges:
        reps["n_edges"] = min(spec.n_edges, 256)
    if spec.batch_nodes:
        reps["batch_nodes"] = min(spec.batch_nodes, 8)
    if spec.fanout:
        reps["fanout"] = tuple(min(f, 3) for f in spec.fanout)
    if spec.n_graphs:
        reps["n_graphs"] = min(spec.n_graphs, 4)
    if spec.d_feat:
        reps["d_feat"] = min(spec.d_feat, 8)
    if spec.batch:
        reps["batch"] = min(spec.batch, 4)
    if spec.n_candidates:
        reps["n_candidates"] = min(spec.n_candidates, 128)
    return dataclasses.replace(spec, **reps)
