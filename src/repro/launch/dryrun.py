import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import — jax locks the device count at first init.
__doc__ = """Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any jax import — jax locks the device
count at first init. This proves, without hardware, that the distribution
config is coherent: shardings divide, collectives partition, the program
compiles; memory_analysis/cost_analysis feed EXPERIMENTS.md §Dry-run and
§Roofline.

Usage:
    python -m repro.launch.dryrun --arch yi-9b --shape train_4k --mesh single
    python -m repro.launch.dryrun --arch hipbone_n15 --mesh multi
    python -m repro.launch.dryrun --all --out results/dryrun.json
"""

import argparse
import functools
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, POISSON, SHAPES, get_config, long_context_eligible
from repro.core.fom import TPU_V5E, nekbone_flops_per_iter
from repro.launch.mesh import flat_mesh, make_production_mesh
from repro.models.blocks import MeshContext
from repro.models.config import ModelConfig
from repro.models.model import decode_step, init_caches, init_model, prefill
from repro.models.params import RULES_TP_DP, RULES_TP_FSDP, tree_shardings_for
from repro.training.optimizer import adafactor
from repro.training.train_step import make_train_step, warmup_cosine

# per-arch training microbatch counts (memory posture; see EXPERIMENTS.md)
MICROBATCHES = {
    "chameleon-34b": 4,
    "command-r-35b": 4,
    "deepseek-v3-671b": 4,   # §Perf iteration C3: activation/dispatch footprint /4
    "yi-9b": 2,
    "mixtral-8x7b": 2,
    "jamba-v0.1-52b": 2,
}

_IS_AXES = lambda x: isinstance(x, tuple) and all(
    isinstance(e, (str, type(None))) for e in x
)


def _dp_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _vocab_axis(cfg, mesh):
    return "model" if cfg.vocab_size % mesh.shape["model"] == 0 else None


def abstract_model(cfg: ModelConfig):
    """(abstract params, logical axes) without allocating anything."""
    box = {}

    def f(k):
        p, a = init_model(cfg, k)
        box["axes"] = a          # python data, captured during tracing
        return p

    params_abs = jax.eval_shape(f, jax.random.key(0))
    return params_abs, box["axes"]


def _shards(abstract_tree, sharding_tree):
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abstract_tree,
        sharding_tree,
    )


def _replicated(mesh, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, P())
        ),
        tree,
    )


def _opt_axes(params_axes):
    def v_for(a):
        if len(a) >= 2:
            return {"vr": tuple(a[:-1]), "vc": tuple(a[:-2]) + (a[-1],)}
        return {"v": tuple(a)}

    return {
        "m": params_axes,
        "v": jax.tree.map(v_for, params_axes, is_leaf=_IS_AXES),
        "count": (),
    }


def _cache_shardings(mesh, caches_abs, batch_axes, seq_axes):
    b_ax = batch_axes if batch_axes else None
    seq = (
        seq_axes
        if len(seq_axes) > 1
        else (seq_axes[0] if seq_axes else None)
    )

    def spec_for(path, leaf):
        key = getattr(path[-1], "key", "")
        nd = leaf.ndim
        if key in ("k", "v"):          # (L?, B, S, KV, D)
            lead = [None] * (nd - 4)
            return P(*lead, b_ax, seq, None, None)
        if key in ("c_kv", "k_rope"):  # (L?, B, S, R)
            lead = [None] * (nd - 3)
            return P(*lead, b_ax, seq, None)
        if key == "state":             # (L?, B, H, N, P)
            lead = [None] * (nd - 4)
            return P(*lead, b_ax, None, None, None)
        if key == "conv":              # (L?, B, W, C)
            lead = [None] * (nd - 3)
            return P(*lead, b_ax, None, None)
        return P(*([None] * nd))

    flat, treedef = jax.tree_util.tree_flatten_with_path(caches_abs)
    return jax.tree_util.tree_unflatten(
        treedef, [NamedSharding(mesh, spec_for(p, l)) for p, l in flat]
    )


def _analyse(lowered, compiled, *, chips, model_flops, extra=None):
    from repro.roofline.hlo_model import analyze_hlo

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):  # old jax returns [dict], new a dict
        cost = cost[0] if cost else {}
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = lowered.as_text()

    # loop-aware accounting (XLA's cost_analysis counts while bodies once;
    # verified in EXPERIMENTS.md §Dry-run)
    st = analyze_hlo(hlo)
    t_compute = st.flops / TPU_V5E.peak_flops
    t_memory = st.hbm_bytes / TPU_V5E.hbm_bandwidth
    t_coll = st.total_link_bytes / TPU_V5E.ici_bandwidth
    terms = {"compute_s": t_compute, "memory_s": t_memory, "collective_s": t_coll}
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    roof = {
        **terms,
        "dominant": dominant,
        "hlo_dot_flops_per_chip": st.flops,
        "hlo_bytes_per_chip_proxy": st.hbm_bytes,
        "link_bytes_per_chip": st.total_link_bytes,
        "collective_counts": st.coll_counts,
        "collective_link_bytes": st.coll_link_bytes,
        "scan_trip_counts": st.trip_counts,
        "roofline_bound_s": bound,
        "model_flops": model_flops,
        "model_flops_per_chip": model_flops / chips,
        "useful_flop_fraction": (model_flops / chips / st.flops) if st.flops else 0.0,
        "roofline_fraction": (
            (model_flops / chips / TPU_V5E.peak_flops) / bound if bound > 0 else 0.0
        ),
        # naive (loop-unaware) reference values from XLA's own counters
        "xla_cost_flops": float(cost.get("flops", 0.0)),
        "xla_cost_bytes": float(cost.get("bytes accessed", 0.0)),
    }
    rec = {
        "status": "ok",
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "total_nonaliased_bytes": mem.argument_size_in_bytes
            + mem.output_size_in_bytes
            + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes,
        },
        "roofline": roof,
    }
    if extra:
        rec.update(extra)
    return rec


# --------------------------------------------------------------------------
# LM cells
# --------------------------------------------------------------------------
def run_lm_cell(arch: str, shape: str, mesh_kind: str) -> dict:
    cfg = get_config(arch)
    sh = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = int(np.prod(mesh.devices.shape))
    dp = _dp_axes(mesh)
    seq_len, gb, step_kind = sh["seq_len"], sh["global_batch"], sh["step"]

    if shape == "long_500k" and not long_context_eligible(cfg):
        return {"status": "skipped", "reason": "full attention; DESIGN.md skip list"}

    # training stores params "assembled" (FSDP over dp axes, paper C1);
    # inference keeps them TP-sharded + dp-replicated: weight-stationary
    # serving has no per-layer all-gather (§Perf iteration 3)
    rules = dict(RULES_TP_FSDP if step_kind == "train" else RULES_TP_DP)
    params_abs, axes = abstract_model(cfg)
    params_sh = tree_shardings_for(params_abs, axes, rules, mesh)
    params_in = _shards(params_abs, params_sh)
    t0 = time.time()

    if step_kind == "train":
        mc = MeshContext(
            mesh=mesh, batch_axes=dp, tp_axis="model", act_seq_axis="model"
        )
        opt = adafactor()
        opt_abs = jax.eval_shape(opt.init, params_abs)
        opt_sh = tree_shardings_for(opt_abs, _opt_axes(axes), rules, mesh)
        opt_in = _shards(opt_abs, opt_sh)
        mb = MICROBATCHES.get(arch, 1)
        step = make_train_step(
            cfg, opt, warmup_cosine(peak_lr=1e-4, warmup=100, total=10000),
            mc, microbatches=mb,
        )
        batch_in = {
            "tokens": jax.ShapeDtypeStruct(
                (gb, seq_len + 1), jnp.int32,
                sharding=NamedSharding(mesh, P(dp, None)),
            )
        }
        idx = jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P()))
        metrics_keys = ["nll", "aux", "loss", "grad_norm", "lr"]
        if cfg.mtp_depth:
            metrics_keys.insert(2, "mtp_nll")
        out_shardings = (
            params_sh,
            opt_sh,
            {k: NamedSharding(mesh, P()) for k in metrics_keys},
        )
        lowered = jax.jit(step, out_shardings=out_shardings).lower(
            params_in, opt_in, batch_in, idx
        )
        model_flops = 6.0 * cfg.n_active_params() * gb * seq_len
    elif step_kind == "prefill":
        mc = MeshContext(mesh=mesh, batch_axes=dp, tp_axis="model")
        caches_abs = jax.eval_shape(lambda: init_caches(cfg, gb, seq_len))
        cache_sh = _cache_shardings(mesh, caches_abs, dp, ("model",))
        tokens_in = jax.ShapeDtypeStruct(
            (gb, seq_len), jnp.int32, sharding=NamedSharding(mesh, P(dp, None))
        )
        fn = functools.partial(prefill, cfg=cfg, mc=mc)
        out_shardings = (NamedSharding(mesh, P(dp, None, _vocab_axis(cfg, mesh))), cache_sh)
        lowered = jax.jit(fn, out_shardings=out_shardings).lower(
            params_in, tokens_in
        )
        model_flops = 2.0 * cfg.n_active_params() * gb * seq_len
    else:  # decode
        if shape == "long_500k":
            batch_axes: tuple = ()
            seq_axes = ("pod", "data", "model") if mesh_kind == "multi" else (
                "data", "model"
            )
        else:
            batch_axes = dp
            seq_axes = ("model",)
        mc = MeshContext(
            mesh=mesh, batch_axes=batch_axes, tp_axis="model", seq_axes=seq_axes
        )
        caches_abs = jax.eval_shape(lambda: init_caches(cfg, gb, seq_len))
        cache_sh = _cache_shardings(mesh, caches_abs, batch_axes, seq_axes)
        caches_in = _shards(caches_abs, cache_sh)
        b_ax = batch_axes if batch_axes else None
        token_in = jax.ShapeDtypeStruct(
            (gb, 1), jnp.int32, sharding=NamedSharding(mesh, P(b_ax, None))
        )
        t_in = jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P()))
        fn = functools.partial(decode_step, cfg=cfg, mc=mc)
        out_shardings = (NamedSharding(mesh, P(b_ax, None, _vocab_axis(cfg, mesh))), cache_sh)
        lowered = jax.jit(fn, out_shardings=out_shardings).lower(
            params_in, token_in, t_in, caches_in
        )
        model_flops = 2.0 * cfg.n_active_params() * gb  # one token per slot

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower
    return _analyse(
        lowered, compiled, chips=chips, model_flops=model_flops,
        extra={
            "arch": arch, "shape": shape, "mesh": mesh_kind, "chips": chips,
            "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
            "params_b": cfg.n_params(), "active_params_b": cfg.n_active_params(),
        },
    )


# --------------------------------------------------------------------------
# hipBone cells (extra, beyond the 40)
# --------------------------------------------------------------------------
def run_poisson_cell(name: str, mesh_kind: str) -> dict:
    from repro.comms.topology import ProcessGrid, factor3
    from repro.core.distributed import DistPoisson, _local_l2g, dist_cg
    from repro.core import sem

    pc = POISSON[name]
    # fp64 presets (mixed-precision cells) need x64, else jit canonicalizes
    # every fp64 aval to fp32 and the lowered HLO measures the wrong program
    if jnp.dtype(pc.dtype) == jnp.float64 or (
        pc.precond_dtype is not None
        and jnp.dtype(pc.precond_dtype) == jnp.float64
    ):
        jax.config.update("jax_enable_x64", True)
    prod = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    mesh = flat_mesh(prod)
    chips = int(np.prod(mesh.devices.shape))
    grid = ProcessGrid(factor3(chips))
    n = pc.n_degree
    bx, by, bz = pc.local_elems
    l2g, halo = _local_l2g(n, pc.local_elems)
    e_loc, p = l2g.shape
    m3 = (bx * n + 1) * (by * n + 1) * (bz * n + 1)
    dtype = jnp.dtype(pc.dtype)

    prob = DistPoisson(
        grid=grid, axis_name="ranks", n_degree=n, local_shape=pc.local_elems,
        box_shape=(bx * n + 1, by * n + 1, bz * n + 1), lam=pc.lam,
        halo_elems=halo, l2g=l2g,
        d=jnp.asarray(sem.derivative_matrix(n), dtype),
        g=jax.ShapeDtypeStruct(
            (chips, e_loc, 6, p), dtype,
            sharding=NamedSharding(mesh, P("ranks")),
        ),
        w_local=jax.ShapeDtypeStruct(
            (chips, e_loc, p), dtype, sharding=NamedSharding(mesh, P("ranks"))
        ),
        mask=jax.ShapeDtypeStruct(
            (chips, m3), dtype, sharding=NamedSharding(mesh, P("ranks"))
        ),
        dtype=dtype,
    )
    b_in = jax.ShapeDtypeStruct(
        (chips, m3), dtype, sharding=NamedSharding(mesh, P("ranks"))
    )
    t0 = time.time()
    run = dist_cg(
        prob, mesh, b_in, n_iter=pc.n_iter, tol=pc.tol,
        precond=pc.precond, cheb_degree=pc.cheb_degree,
        pmg_smooth_degree=pc.pmg_smooth_degree,
        pmg_smoother=pc.pmg_smoother,
        pmg_coarse_op=pc.pmg_coarse_op,
        pmg_coarse_iters=pc.pmg_coarse_iters,
        schwarz_overlap=pc.schwarz_overlap,
        schwarz_inner_degree=pc.schwarz_inner_degree,
        precond_dtype=pc.precond_dtype,
        cg_variant=pc.cg_variant,
        exchange=pc.exchange,
    )
    lowered = jax.jit(run.func).lower(*run.args)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower
    e_total = chips * e_loc
    model_flops = nekbone_flops_per_iter(e_total, n) * pc.n_iter
    return _analyse(
        lowered, compiled, chips=chips, model_flops=model_flops,
        extra={
            "arch": name, "shape": f"N={n} E/rank={e_loc}", "mesh": mesh_kind,
            "chips": chips, "grid": grid.shape,
            "dofs": chips * pc.dofs_per_rank(),
            "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        },
    )


# --------------------------------------------------------------------------
def all_cells() -> list[tuple[str, str, str]]:
    cells = []
    for arch in ARCHS:
        for shape in SHAPES:
            for mesh_kind in ("single", "multi"):
                cells.append((arch, shape, mesh_kind))
    for name in POISSON:
        for mesh_kind in ("single", "multi"):
            cells.append((name, "-", mesh_kind))
    return cells


def run_cell(arch: str, shape: str, mesh_kind: str) -> dict:
    try:
        if arch in POISSON:
            return run_poisson_cell(arch, mesh_kind)
        return run_lm_cell(arch, shape, mesh_kind)
    except Exception as e:  # a failure here is a bug in the system
        return {
            "status": "failed",
            "arch": arch, "shape": shape, "mesh": mesh_kind,
            "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-2000:],
        }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", help="architecture id (or hipbone_*)")
    ap.add_argument("--shape", default="train_4k", choices=list(SHAPES) + ["-"])
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()

    if args.list:
        for c in all_cells():
            print(*c)
        return

    results = []
    if args.all:
        cells = all_cells()
    else:
        cells = [(args.arch, args.shape, args.mesh)]
    for arch, shape, mesh_kind in cells:
        print(f"=== {arch} x {shape} x {mesh_kind} ===", flush=True)
        rec = run_cell(arch, shape, mesh_kind)
        show = {k: v for k, v in rec.items() if k != "traceback"}
        print(json.dumps(show, indent=2, default=str), flush=True)
        results.append(rec)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=2, default=str)
        print(f"wrote {args.out}")
    n_fail = sum(r.get("status") == "failed" for r in results)
    if n_fail:
        raise SystemExit(f"{n_fail} cells FAILED")


if __name__ == "__main__":
    main()
