import os

# Merge, don't clobber: the user's own XLA_FLAGS (dump paths, autotune
# knobs) must survive; only the host-device-count flag is replaced — the
# dry-run's mesh math requires exactly 512 host devices. MUST run before
# any jax import: jax locks the device count at first initialization
# (see MULTI-POD DRY-RUN spec).
_flags = [
    f
    for f in os.environ.get("XLA_FLAGS", "").split()
    if not f.startswith("--xla_force_host_platform_device_count")
]
_flags.append("--xla_force_host_platform_device_count=512")
os.environ["XLA_FLAGS"] = " ".join(_flags)
del _flags

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.analysis.flops import cell_cost  # noqa: E402
from repro.analysis.hlo import collective_bytes  # noqa: E402
from repro.configs.registry import ARCH_IDS, get_config  # noqa: E402
from repro.configs.shapes import SHAPES, applicable  # noqa: E402
from repro.dist.sharding import (  # noqa: E402
    batch_specs,
    cache_specs,
    clear_hints,
    opt_specs,
    param_specs,
    set_hints,
    to_named,
)
from repro.launch.input_specs import (  # noqa: E402
    batch_sds,
    decode_sds,
    opt_sds,
    params_sds,
    tree_bytes,
)
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.serve.decode import make_prefill_step, make_serve_step  # noqa: E402
from repro.train.step import make_train_step  # noqa: E402

RESULTS_DIR = Path(
    os.environ.get(
        "REPRO_RESULTS_DIR",
        Path(__file__).resolve().parents[3] / "results" / "dryrun",
    )
)


def micro_batches_for(cfg, shape, mesh) -> int:
    """Pick gradient-accumulation depth: ~2 sequences per data shard."""
    dsize = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    local = max(1, shape.global_batch // dsize)
    micro = max(1, local // 2)
    while local % micro:
        micro -= 1
    return micro


def build_and_compile(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    *,
    moe_chunks: int = 1,
    decode_fsdp: bool = True,
    cross_cache: bool = False,
    ep_pods: bool = False,
    accum_bf16: bool = False,
):
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = applicable(cfg, shape)
    rec = dict(
        arch=arch, shape=shape_name,
        mesh="pod2x16x16" if multi_pod else "pod16x16",
        n_devices=int(mesh.size),
    )
    if not ok:
        rec.update(status="skip", reason=reason)
        return rec

    set_hints(mesh, ("pod", "data") if multi_pod else ("data",))
    p_sds = params_sds(cfg)
    fsdp = True if shape.kind == "train" else decode_fsdp
    pspec = param_specs(cfg, p_sds, mesh, fsdp=fsdp, ep_pods=ep_pods)
    pnamed = to_named(mesh, pspec)
    micro = 1
    t0 = time.time()

    if shape.kind == "train":
        micro = micro_batches_for(cfg, shape, mesh)
        fn = make_train_step(
            cfg, micro_batches=micro, moe_chunks=moe_chunks,
            accum_dtype=jnp.bfloat16 if accum_bf16 else jnp.float32,
        )
        o_sds = opt_sds(p_sds)
        onamed = to_named(mesh, opt_specs(pspec))
        b_sds = batch_sds(cfg, shape)
        bnamed = to_named(mesh, batch_specs(cfg, mesh, b_sds))
        jf = jax.jit(
            fn,
            in_shardings=(pnamed, onamed, bnamed),
            out_shardings=(pnamed, onamed, None),
            donate_argnums=(0, 1),
        )
        lowered = jf.lower(p_sds, o_sds, b_sds)
    elif shape.kind == "prefill":
        fn = make_prefill_step(cfg, moe_chunks=moe_chunks)
        b_sds = batch_sds(cfg, shape)
        bnamed = to_named(mesh, batch_specs(cfg, mesh, b_sds))
        jf = jax.jit(fn, in_shardings=(pnamed, bnamed))
        lowered = jf.lower(p_sds, b_sds)
    else:  # decode
        d = decode_sds(cfg, shape)
        cnamed = to_named(mesh, cache_specs(cfg, mesh, d["cache"]))
        tnamed = to_named(mesh, batch_specs(cfg, mesh, {"tokens": d["tokens"]}))["tokens"]
        serve = make_serve_step(cfg, moe_chunks=moe_chunks)
        args = [p_sds, d["cache"], d["tokens"], d["pos"]]
        in_sh = [pnamed, cnamed, tnamed, None]
        if "enc_out" in d:
            if cross_cache:
                # §Perf variant: precomputed cross-K/V instead of raw memory
                from repro.serve.decode import make_cross_cache

                cc_sds = jax.eval_shape(
                    lambda p, e: make_cross_cache(p, cfg, e), p_sds, d["enc_out"]
                )
                args.append(None)   # enc_out unused
                in_sh.append(None)
                args.append(cc_sds)
                in_sh.append(to_named(mesh, cache_specs(cfg, mesh, cc_sds)))
            else:
                args.append(d["enc_out"])
                in_sh.append(
                    to_named(mesh, batch_specs(cfg, mesh, {"e": d["enc_out"]}))["e"]
                )
        jf = jax.jit(
            serve,
            in_shardings=tuple(in_sh),
            out_shardings=(None, None, cnamed),
            donate_argnums=(1,),
        )
        lowered = jf.lower(*args)

    compiled = lowered.compile()
    clear_hints()
    compile_s = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text())
    ana = cell_cost(cfg, shape, micro_batches=micro)

    mem_rec = {}
    for attr in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "generated_code_size_in_bytes",
        "alias_size_in_bytes",
        "peak_memory_in_bytes",
    ):
        v = getattr(mem, attr, None)
        if v is not None:
            mem_rec[attr] = int(v)

    rec.update(
        status="ok",
        memory=mem_rec,
        micro_batches=micro,
        compile_s=round(compile_s, 1),
        hlo_flops_raw=float(cost.get("flops", -1.0)),
        hlo_bytes_raw=float(cost.get("bytes accessed", -1.0)),
        collective_bytes_per_device=coll,
        analytic_flops=ana.flops,
        analytic_hbm_bytes=ana.hbm_bytes,
        model_flops=ana.model_flops,
        param_bytes_global=tree_bytes(p_sds),
    )
    return rec


def cell_path(arch, shape_name, multi_pod) -> Path:
    mesh = "pod2" if multi_pod else "pod1"
    return RESULTS_DIR / f"{arch}__{shape_name}__{mesh}.json"


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", nargs="*", default=ARCH_IDS)
    ap.add_argument("--shape", nargs="*", default=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--moe-chunks", type=int, default=1,
                    help="chunk-local MoE dispatch (perf variant; = data shards)")
    ap.add_argument("--no-fsdp-decode", action="store_true",
                    help="TP-only params for decode cells (perf variant)")
    ap.add_argument("--cross-cache", action="store_true",
                    help="precomputed cross-K/V for enc-dec decode (perf variant)")
    ap.add_argument("--ep-pods", action="store_true",
                    help="expert parallelism across the pod axis too (perf variant)")
    ap.add_argument("--accum-bf16", action="store_true",
                    help="bf16 gradient accumulation (perf variant)")
    ap.add_argument("--suffix", default="",
                    help="result-file suffix for perf variants")
    args = ap.parse_args()

    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    pods = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    failures = 0
    for arch in args.arch:
        for shape_name in args.shape:
            for multi_pod in pods:
                path = cell_path(arch, shape_name, multi_pod)
                if args.suffix:
                    path = path.with_name(path.stem + "__" + args.suffix + ".json")
                if path.exists() and not args.force:
                    rec = json.loads(path.read_text())
                    print(f"[cached] {path.name}: {rec.get('status')}")
                    continue
                label = f"{arch} x {shape_name} x {'pod2' if multi_pod else 'pod1'}"
                print(f"[lower+compile] {label} ...", flush=True)
                try:
                    rec = build_and_compile(
                        arch, shape_name, multi_pod,
                        moe_chunks=args.moe_chunks,
                        decode_fsdp=not args.no_fsdp_decode,
                        cross_cache=args.cross_cache,
                        ep_pods=args.ep_pods,
                        accum_bf16=args.accum_bf16,
                    )
                except Exception as e:  # record failures — they are bugs
                    rec = dict(
                        arch=arch, shape=shape_name,
                        mesh="pod2x16x16" if multi_pod else "pod16x16",
                        status="error", error=f"{type(e).__name__}: {e}",
                        trace=traceback.format_exc()[-2000:],
                    )
                    failures += 1
                path.write_text(json.dumps(rec, indent=1))
                print(f"  -> {rec['status']}" + (
                    f" compile={rec.get('compile_s')}s flops={rec.get('hlo_flops_raw'):.3g}"
                    if rec["status"] == "ok" else f" ({rec.get('reason', rec.get('error'))})"
                ), flush=True)
    print(f"done; failures={failures}")
    return failures


if __name__ == "__main__":
    raise SystemExit(main())
