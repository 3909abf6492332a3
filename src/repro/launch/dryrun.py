import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
mesh with ShapeDtypeStruct stand-ins (no allocation), and extract the roofline
terms from the compiled artifact.

  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2_9b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--fast]

Results land in experiments/dryrun/<arch>__<shape>__<mesh>.json. The two
os.environ lines above MUST stay the first statements in this module — jax
locks the device count on first init (see the build brief).
"""
import argparse
import json
import pathlib
import time
import traceback

import jax

from repro.configs.registry import get_config, list_archs
from repro.launch import roofline as RL
from repro.launch import specs as SP
from repro.launch.mesh import DRYRUN_KIND, chip_peaks, make_production_mesh
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.parallel import sharding as SH

OUT_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def _abstract_params(cfg: ModelConfig):
    return jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg))


def _abstract_opt(params):
    from repro.optim.optimizers import adamw

    init, _ = adamw(1e-4)
    return jax.eval_shape(init, params)


def _ns(mesh, spec_tree, tree):
    specs = SH.sanitize_specs(mesh, spec_tree, tree)
    return jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s), specs
    )


def lower_pair(cfg: ModelConfig, shape: str, mesh, *, fsdp: bool = False,
               donate: bool = True):
    """Returns (lowered, compiled, meta) for one (arch x shape x mesh)."""
    kind = SP.SHAPES[shape]["kind"]
    cfg_eff = SP.effective_pattern(cfg, shape)
    cfg_eff = SP.mesh_adapt(cfg_eff, mesh.shape["model"])

    if kind == "train":
        from repro.launch.train import TrainState, build_train_step

        params = _abstract_params(cfg_eff)
        opt = _abstract_opt(params)
        state = TrainState(params, opt)
        batch = SP.input_specs(cfg_eff, shape)
        pspecs = SH.param_specs(params)
        if fsdp:
            pspecs = _fsdp_specs(pspecs, params)
        ospecs = _fsdp_opt(opt, pspecs) if fsdp else SH.opt_state_specs(opt, params)
        state_specs = TrainState(pspecs, ospecs)
        step = build_train_step(cfg_eff, mesh=mesh)
        jitted = jax.jit(
            step,
            in_shardings=(
                _ns(mesh, state_specs, state),
                _ns(mesh, SH.batch_specs(mesh, batch), batch),
            ),
            donate_argnums=(0,) if donate else (),
        )
        with mesh:
            lowered = jitted.lower(state, batch)

    elif kind == "prefill":
        params = _abstract_params(cfg_eff)
        batch = SP.input_specs(cfg_eff, shape)
        pspecs = SH.param_specs(params)
        if fsdp:
            pspecs = _fsdp_specs(pspecs, params)
        fn = lambda p, b: M.prefill(p, cfg_eff, b, mesh=mesh)
        jitted = jax.jit(
            fn,
            in_shardings=(
                _ns(mesh, pspecs, params),
                _ns(mesh, SH.batch_specs(mesh, batch), batch),
            ),
        )
        with mesh:
            lowered = jitted.lower(params, batch)

    else:  # decode
        params = _abstract_params(cfg_eff)
        token, pos, cache = SP.decode_specs(cfg_eff, shape)
        pspecs = SH.param_specs(params)
        if getattr(cfg_eff, "moe_2d", False):
            pspecs = _moe_2d_specs(pspecs, params)
        if fsdp:
            pspecs = _fsdp_specs(pspecs, params)
        fn = lambda p, t, i, c: M.decode_step(p, cfg_eff, t, i, c, mesh=mesh)
        cache_sh = _ns(mesh, SH.cache_specs(mesh, cache), cache)
        jitted = jax.jit(
            fn,
            in_shardings=(
                _ns(mesh, pspecs, params),
                _ns(mesh, SH.batch_specs(mesh, {"t": token}), {"t": token})["t"],
                None,
                cache_sh,
            ),
            # matching output shardings let XLA alias the donated cache
            # (inferred shardings diverged -> a full extra cache copy, §Perf)
            out_shardings=(None, cache_sh),
            donate_argnums=(3,) if donate else (),
        )
        with mesh:
            lowered = jitted.lower(params, token, pos, cache)

    compiled = lowered.compile()
    return lowered, compiled, {"kind": kind}


def _fsdp_specs(pspecs, params):
    """Add 'data'-axis sharding on the first free dim of >=2D weights (ZeRO-3
    flavoured storage sharding; GSPMD all-gathers at use)."""
    from jax.sharding import PartitionSpec as P

    def leaf(spec, arr):
        dims = list(spec) + [None] * (arr.ndim - len(spec))
        if arr.ndim < 2 or max(arr.shape) < 4096:
            return spec
        if any(d == "data" or (isinstance(d, tuple) and "data" in d) for d in dims):
            return spec  # already data-sharded (e.g. moe_2d expert layout)
        for i, d in enumerate(dims):
            if d is None and arr.shape[i] % 16 == 0:
                dims[i] = "data"
                return P(*dims)
        return spec

    return jax.tree.map(leaf, pspecs, params)


def _moe_2d_specs(pspecs, params):
    """Expert tensors -> experts on 'model' x d_ff on 'data' (matches
    models.moe.moe_ffn_2d's shard_map in_specs)."""
    from jax.sharding import PartitionSpec as P

    def leaf(path, spec, arr):
        names = [p.key for p in path if isinstance(p, jax.tree_util.DictKey)]
        name = names[-1] if names else ""
        if arr.ndim == 4 and name in ("w_gate", "w_up", "w_down") and "ffn" in names:
            # stacked (period, E, d/f, f/d)
            if name == "w_down":
                return P(None, "model", "data", None)
            return P(None, "model", None, "data")
        return spec

    return jax.tree_util.tree_map_with_path(leaf, pspecs, params)


def _fsdp_opt(opt, pspecs):
    from jax.sharding import PartitionSpec as P
    from repro.optim.optimizers import OptState

    return OptState(step=P(), mu=pspecs, nu=pspecs)


def run_pair(arch: str, shape: str, *, multi_pod: bool = False,
             fsdp: bool = False, verbose: bool = True,
             variant: dict | None = None) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "16x16"
    cfg = get_config(arch)
    if variant:
        cfg = cfg.scaled(**variant)
    skip = SP.shape_skip_reason(cfg, shape)
    rec = {
        "arch": arch, "shape": shape, "mesh": mesh_name,
        "fsdp": fsdp, "time_s": 0.0, "variant": variant or {},
    }
    if skip:
        rec["status"] = "skipped"
        rec["reason"] = skip
        return rec

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    # >50B models cannot hold params (+optimizer when training) on the model
    # axis alone: 'data'-axis weight sharding is the only sane baseline
    # (noted in EXPERIMENTS.md). With the 2D expert layout the experts are
    # already data-sharded and the residual weights fit — skip blanket FSDP.
    if cfg.param_count() > 50e9 and not getattr(cfg, "moe_2d", False):
        fsdp = True
    rec["fsdp"] = fsdp
    try:
        lowered, compiled, meta = lower_pair(cfg, shape, mesh, fsdp=fsdp)
        cost_naive = compiled.cost_analysis()
        memd = RL.memory_dict(compiled)
        hlo_text = compiled.as_text()
        from repro.launch import hlo_cost

        corrected = hlo_cost.analyze(hlo_text)   # loop-aware (trip counts)
        cost = {
            "flops": corrected["flops"],
            "bytes accessed": corrected["hbm_bytes"],
        }
        coll = {
            "total": corrected["collective_bytes"],
            "counts": corrected["collective_counts"],
        }
        rl = RL.roofline(cost, memd, coll, DRYRUN_KIND)
        rl["xla_cost_analysis_flops_uncorrected"] = float(cost_naive.get("flops", 0.0))
        mf = RL.model_flops(cfg, SP.SHAPES[shape], meta["kind"])
        hlo_global = rl["hlo_flops_per_dev"] * n_chips
        rec.update(
            status="ok",
            kind=meta["kind"],
            chips=n_chips,
            roofline=rl,
            model_flops_global=mf,
            useful_flops_ratio=(mf / hlo_global) if hlo_global else None,
            fits_hbm=memd["total_hbm_bytes"] <= chip_peaks(DRYRUN_KIND).hbm_bytes,
            hbm_gib=memd["total_hbm_bytes"] / 1024**3,
            collective_counts=coll["counts"],
            swa_variant=SP.uses_swa_variant(cfg, shape),
        )
        if verbose:
            print(f"  memory_analysis: {memd}")
            print(f"  cost_analysis: flops={cost.get('flops', 0):.3e} "
                  f"bytes={cost.get('bytes accessed', 0):.3e}")
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["trace"] = traceback.format_exc()[-2000:]
    rec["time_s"] = round(time.time() - t0, 1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--variant", action="append", default=[],
                    help="cfg override key=value (int/bool/float autocast)")
    ap.add_argument("--tag", default=None, help="suffix for the output JSON")
    args = ap.parse_args()

    variant = {}
    for kv in args.variant:
        k, v = kv.split("=", 1)
        if v in ("true", "True"):
            v = True
        elif v in ("false", "False"):
            v = False
        else:
            try:
                v = int(v)
            except ValueError:
                try:
                    v = float(v)
                except ValueError:
                    pass
        variant[k] = v

    OUT_DIR.mkdir(parents=True, exist_ok=True)
    pairs = (
        [(a, s) for a in list_archs() for s in SP.SHAPES]
        if args.all
        else [(args.arch, args.shape)]
    )
    mesh_name = "pod2x16x16" if args.multi_pod else "16x16"
    for arch, shape in pairs:
        tag = f"{arch}__{shape}__{mesh_name}" + ("__fsdp" if args.fsdp else "")
        if args.tag:
            tag += f"__{args.tag}"
        path = OUT_DIR / f"{tag}.json"
        if args.skip_existing and path.exists():
            print(f"[skip existing] {tag}")
            continue
        print(f"[dryrun] {tag}")
        rec = run_pair(arch, shape, multi_pod=args.multi_pod, fsdp=args.fsdp,
                       variant=variant)
        path.write_text(json.dumps(rec, indent=1, default=str))
        status = rec["status"]
        extra = (
            f" dominant={rec['roofline']['dominant']} hbm={rec['hbm_gib']:.1f}GiB"
            if status == "ok" else f" ({rec.get('reason') or rec.get('error', '')[:120]})"
        )
        print(f"  -> {status} in {rec['time_s']}s{extra}")


if __name__ == "__main__":
    main()
