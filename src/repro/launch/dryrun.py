import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import: jax locks the device
# count at first initialization, and the multi-pod dry-run needs 512
# placeholder host devices to build the production meshes.

import argparse      # noqa: E402
import gzip          # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCHS, get_config              # noqa: E402
from repro.distributed import params as pshard           # noqa: E402
from repro.distributed.sharding import use_rules         # noqa: E402
from repro.distributed.steps import (make_prefill_step,  # noqa: E402
                                     make_serve_step, make_train_step)
from repro.launch import shapes as shp                   # noqa: E402
from repro.launch.mesh import make_production_mesh       # noqa: E402
from repro.models import lm                              # noqa: E402
from repro.optim import adamw_init                       # noqa: E402

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "benchmarks", "out", "dryrun")

# grad-accumulation per architecture (train_4k): bounds activation memory.
# Values tuned by the section-Perf iterations (EXPERIMENTS.md): the
# per-device remat carry is (mb/16, S, d_model) bf16 per layer, so accum
# rises with L * d_model until temp fits the 16 GiB v5e HBM.
ACCUM = {
    "command_r_plus_104b": 8, "deepseek_coder_33b": 8, "granite_20b": 4,
    "phi35_moe_42b": 8, "llava_next_mistral_7b": 2,
    "rwkv6_3b": 2, "recurrentgemma_2b": 2, "olmo_1b": 1,
    "granite_moe_1b": 1, "whisper_small": 1,
}

# ZeRO-1 (bf16 params replicated over `data`, fp32 master+moments sharded):
# kills the per-layer per-microbatch FSDP weight all-gathers that dominated
# the baseline collective term (EXPERIMENTS.md section Perf, iteration 4).
# command-r-plus's bf16 weights alone are 13 GiB per model shard, which
# cannot be replicated over the data axis on 16 GiB v5e -> it stays FSDP
# (at 104B on 256 chips the production answer is pipeline parallelism).
ZERO1 = {
    "deepseek_coder_33b": True, "command_r_plus_104b": False,
    "olmo_1b": True, "granite_20b": True, "phi35_moe_42b": True,
    "granite_moe_1b": True, "recurrentgemma_2b": True,
    "llava_next_mistral_7b": True, "rwkv6_3b": True, "whisper_small": True,
}

# sequence-parallel residual stream: a memory/collective trade-off (an
# all-gather + reduce-scatter pair per layer per microbatch buys a
# model-axis-fold reduction of the remat carries).  Only the architectures
# whose activations would otherwise exceed HBM keep it on (section Perf
# iteration 5): small/narrow models are cheaper without it.
SEQPAR = {
    "command_r_plus_104b": True, "deepseek_coder_33b": True,
    "phi35_moe_42b": True, "llava_next_mistral_7b": True,
    "granite_20b": True, "granite_moe_1b": True,
    "recurrentgemma_2b": True, "rwkv6_3b": True,
    # measured cheaper without it (activations already fit):
    "olmo_1b": False, "whisper_small": False,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-device bytes by collective op, parsed from the post-SPMD HLO."""
    out = {c: 0 for c in _COLLECTIVES}
    counts = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        if ls.startswith("%") or ls.startswith("ROOT"):
            m = re.search(r"=\s+(\S.*?)\s+([a-z0-9-]+)\(", ls)
            if not m:
                continue
            type_str, op = m.group(1), m.group(2)
            base = None
            for c in _COLLECTIVES:
                if op == c or op.startswith(c + "-"):
                    base = c
                    break
            if base is None:
                continue
            out[base] += _shape_bytes(type_str)
            counts[base] += 1
    return {"bytes": out, "counts": counts}


def _flatten_cost(cost) -> dict:
    return {k: float(v)
            for k, v in cost.items()
            if isinstance(v, (int, float))}


def _mem_analysis(compiled) -> dict:
    try:
        m = compiled.memory_analysis()
    except Exception:
        return {}
    if m is None:
        return {}
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    out = {}
    for k in keys:
        v = getattr(m, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def _specs_to_shardings(tree, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                        is_leaf=lambda x: isinstance(x, P))


def build_cell(arch: str, shape_name: str, mesh, *, q_chunk: int = 1024):
    """Returns (jitted_fn, arg_sds) for one (arch x shape) cell."""
    import dataclasses

    cfg = dataclasses.replace(get_config(arch), param_dtype="bfloat16")
    shape = shp.SHAPES[shape_name]
    ok, why = shp.cell_supported(cfg, shape)
    if not ok:
        return None, why
    zero1 = ZERO1.get(arch, True)

    abstract_params = jax.eval_shape(
        lambda: lm.init_params(jax.random.key(0), cfg))
    pspec = pshard.param_specs(abstract_params, mesh, zero1=zero1)
    psh = _specs_to_shardings(pspec, mesh)

    if shape.kind == "train":
        accum = ACCUM.get(arch, 1)
        abstract_opt = jax.eval_shape(
            lambda p: adamw_init(p, master=True), abstract_params)
        ospec = pshard.opt_state_specs(abstract_opt, abstract_params, mesh,
                                       zero1=zero1)
        osh = _specs_to_shardings(ospec, mesh)
        grad_sh = _specs_to_shardings(
            pshard.param_specs(abstract_params, mesh), mesh) if zero1 \
            else None
        step = make_train_step(cfg, accum_steps=accum, q_chunk=q_chunk,
                               grad_shardings=grad_sh)
        batch_sds = shp.input_specs(cfg, shape)
        bspec = pshard.batch_specs(batch_sds, mesh)
        bsh = _specs_to_shardings(bspec, mesh)
        # params/opt are consumed and re-emitted every step: donate them
        jitted = jax.jit(step, in_shardings=(psh, osh, bsh),
                         out_shardings=(psh, osh, None),
                         donate_argnums=(0, 1))
        args = (abstract_params, abstract_opt, batch_sds)
    elif shape.kind == "prefill":
        step = make_prefill_step(cfg, cache_len=shape.seq_len,
                                 q_chunk=q_chunk)
        batch_sds = shp.input_specs(cfg, shape)
        bspec = pshard.batch_specs(batch_sds, mesh)
        bsh = _specs_to_shardings(bspec, mesh)
        abstract_cache = jax.eval_shape(
            lambda: lm.init_cache(cfg, shape.global_batch, shape.seq_len,
                                  jnp.bfloat16))
        cspec = pshard.cache_specs(abstract_cache, cfg, mesh)
        csh = _specs_to_shardings(cspec, mesh)
        jitted = jax.jit(step, in_shardings=(psh, bsh),
                         out_shardings=(None, csh))
        args = (abstract_params, batch_sds)
    else:  # decode
        step = make_serve_step(cfg)
        specs = shp.input_specs(cfg, shape)
        cspec = pshard.cache_specs(specs["cache"], cfg, mesh)
        csh = _specs_to_shardings(cspec, mesh)
        tok_sh = NamedSharding(mesh, pshard.batch_specs(
            specs["tokens"], mesh))
        pos_sh = NamedSharding(mesh, P())
        # donate the cache: serving updates it in place (halves cache HBM)
        jitted = jax.jit(step, in_shardings=(psh, csh, tok_sh, pos_sh),
                         out_shardings=(tok_sh, None, csh),
                         donate_argnums=(1,))
        args = (abstract_params, specs["cache"], specs["tokens"],
                specs["pos"])
    return (jitted, args), None


def run_cell(arch: str, shape_name: str, mesh_kind: str, *,
             save_hlo: bool = True) -> dict:
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    row = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "mesh_shape": list(dict(zip(mesh.axis_names,
                                       mesh.devices.shape)).items())}
    rules = {} if SEQPAR.get(arch, True) else {"seq_resid": None}
    with use_rules(mesh, rules):
        built, why = build_cell(arch, shape_name, mesh)
        if built is None:
            row.update(status="skipped", reason=why)
            return row
        jitted, args = built
        lowered = jitted.lower(*args)
        t_lower = time.time()
        compiled = lowered.compile()
        t_compile = time.time()
    hlo = compiled.as_text()
    row.update(
        status="ok",
        lower_s=round(t_lower - t0, 1),
        compile_s=round(t_compile - t_lower, 1),
        memory=_mem_analysis(compiled),
        cost=_flatten_cost(compiled.cost_analysis()),
        collectives=collective_bytes(hlo),
        hlo_lines=hlo.count("\n"),
    )
    if save_hlo:
        os.makedirs(OUT_DIR, exist_ok=True)
        with gzip.open(os.path.join(
                OUT_DIR, f"{arch}__{shape_name}__{mesh_kind}.hlo.gz"),
                "wt") as f:
            f.write(hlo)
    return row


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", nargs="*", default=list(ARCHS))
    ap.add_argument("--shape", nargs="*", default=list(shp.SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="both")
    ap.add_argument("--force", action="store_true",
                    help="recompute cells that already have results")
    ap.add_argument("--no-save-hlo", action="store_true")
    args = ap.parse_args()

    os.makedirs(OUT_DIR, exist_ok=True)
    meshes = {"single": ["single"], "multi": ["multi"],
              "both": ["single", "multi"]}[args.mesh]
    failures = 0
    for arch in args.arch:
        for shape_name in args.shape:
            for mesh_kind in meshes:
                path = os.path.join(
                    OUT_DIR, f"{arch}__{shape_name}__{mesh_kind}.json")
                if os.path.exists(path) and not args.force:
                    print(f"[skip-cached] {arch} {shape_name} {mesh_kind}")
                    continue
                try:
                    row = run_cell(arch, shape_name, mesh_kind,
                                   save_hlo=not args.no_save_hlo)
                except Exception as e:
                    traceback.print_exc()
                    row = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_kind, "status": "error",
                           "error": f"{type(e).__name__}: {e}"}
                    failures += 1
                with open(path, "w") as f:
                    json.dump(row, f, indent=1)
                mem = row.get("memory", {})
                cost = row.get("cost", {})
                print(f"[{row['status']:7s}] {arch} {shape_name} {mesh_kind} "
                      f"lower={row.get('lower_s', 0)}s "
                      f"compile={row.get('compile_s', 0)}s "
                      f"args={mem.get('argument_size_in_bytes', 0) / 2**30:.2f}GiB "
                      f"temp={mem.get('temp_size_in_bytes', 0) / 2**30:.2f}GiB "
                      f"flops={cost.get('flops', 0):.3g}",
                      flush=True)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
