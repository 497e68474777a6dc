import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
# ^ MUST precede any jax import: jax locks the device count on first init.

_DOC = """Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds abstract params/optimizer/batch/state specs,
attaches the production shardings, lowers the jitted step
(train_step / prefill / decode_step per the shape kind), compiles it, and
records memory_analysis + cost_analysis + the HLO collective-byte breakdown
into a JSON artifact consumed by launch.roofline and EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch yi-34b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod] [--out artifacts/dryrun]
"""

import argparse
import functools
import json
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..configs import SHAPES, all_archs, cells, get_arch
from ..models import lm
from ..training.grad import make_train_step
from ..training.optimizer import AdamWConfig, adamw_init
from . import sharding as sh
from . import specs
from .hlo_analysis import analyze as hlo_analyze
from .mesh import make_production_mesh

TRAIN_MICROBATCHES = 16


def _jsonable(d: Dict[str, Any]) -> Dict[str, Any]:
    out = {}
    for k, v in (d or {}).items():
        try:
            out[k] = float(v)
        except (TypeError, ValueError):
            continue
    return out


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               microbatches: int = TRAIN_MICROBATCHES) -> Dict[str, Any]:
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()

    from ..models import runtime
    runtime.set_mesh(mesh, ("pod", "data") if multi_pod else ("data",))

    params_abs = specs.params_specs(cfg)
    params_sh = sh.params_shardings(mesh, params_abs)

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            opt_abs = jax.eval_shape(adamw_init, params_abs)
            opt_sh = sh.opt_shardings(mesh, opt_abs, params_sh)
            batch_abs = specs.train_batch_specs(cfg, shape)
            batch_sh = sh.batch_shardings(mesh, cfg, shape, batch_abs)
            opt_cfg = AdamWConfig()
            data_ax = ("pod", "data") if multi_pod else ("data",)
            data_size = 32 if multi_pod else 16
            mb = min(microbatches, shape.global_batch // data_size)
            microbatches = mb
            step = make_train_step(cfg, opt_cfg, microbatches=mb,
                                   param_shardings=params_sh,
                                   data_axes=data_ax)
            fn = jax.jit(step,
                         in_shardings=(params_sh, opt_sh, batch_sh),
                         out_shardings=(params_sh, opt_sh, None),
                         donate_argnums=(0, 1))
            lowered = fn.lower(params_abs, opt_abs, batch_abs)
        elif shape.kind == "prefill":
            batch_abs = specs.prefill_batch_specs(cfg, shape)
            batch_sh = sh.batch_shardings(mesh, cfg, shape, batch_abs)
            state_abs = jax.eval_shape(
                functools.partial(lm.prefill, cfg, cache_size=shape.seq_len),
                params_abs, batch_abs)[1]
            state_sh = sh.state_shardings(mesh, cfg, shape, state_abs)
            fn = jax.jit(
                functools.partial(lm.prefill, cfg, cache_size=shape.seq_len),
                in_shardings=(params_sh, batch_sh),
                out_shardings=(sh.logits_sharding(mesh, cfg, shape), state_sh))
            lowered = fn.lower(params_abs, batch_abs)
        else:  # decode
            tok_abs = specs.decode_token_specs(shape)
            state_abs = specs.decode_state_specs(cfg, shape)
            state_sh = sh.state_shardings(mesh, cfg, shape, state_abs)
            tok_sh = sh.batch_shardings(mesh, cfg, shape, tok_abs)
            fn = jax.jit(functools.partial(lm.decode_step, cfg),
                         in_shardings=(params_sh, tok_sh, state_sh),
                         out_shardings=(sh.logits_sharding(mesh, cfg, shape),
                                        state_sh),
                         donate_argnums=(2,))
            lowered = fn.lower(params_abs, tok_abs, state_abs)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    runtime.clear_mesh()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    deep = hlo_analyze(hlo)       # trip-count-aware flops/bytes/collectives

    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "devices": 512 if multi_pod else 256,
        "kind": shape.kind,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": _jsonable({
            "argument_size": getattr(mem, "argument_size_in_bytes", None),
            "output_size": getattr(mem, "output_size_in_bytes", None),
            "temp_size": getattr(mem, "temp_size_in_bytes", None),
            "alias_size": getattr(mem, "alias_size_in_bytes", None),
            "generated_code_size": getattr(mem, "generated_code_size_in_bytes",
                                           None),
        }),
        "cost_raw": {k: v for k, v in _jsonable(
            cost if isinstance(cost, dict) else
            (cost[0] if cost else {})).items()
            if k in ("flops", "bytes accessed", "transcendentals")},
        "flops": deep["flops"],
        "bytes_accessed": deep["bytes_accessed"],
        "collectives": deep["collectives"],
        "params": lm.param_count(cfg),
        "active_params": lm.active_param_count(cfg),
        "microbatches": microbatches if shape.kind == "train" else None,
    }
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--microbatches", type=int, default=TRAIN_MICROBATCHES)
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    todo = []
    if args.all:
        for arch in all_archs():
            if arch == "paper-cftrag":
                continue                      # paper config: not an assigned cell
            todo.extend(cells(arch))
    else:
        todo = [(args.arch, args.shape)]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = []
    for arch, shape_name in todo:
        for mp in meshes:
            tag = f"{arch}__{shape_name}__{'2pod' if mp else '1pod'}"
            out_path = os.path.join(args.out, tag + ".json")
            if os.path.exists(out_path):
                print(f"[skip] {tag} (artifact exists)")
                continue
            print(f"[dryrun] {tag} ...", flush=True)
            try:
                rec = lower_cell(arch, shape_name, mp,
                                 microbatches=args.microbatches)
                with open(out_path, "w") as f:
                    json.dump(rec, f, indent=1)
                per_dev = rec["memory"].get("argument_size", 0) + \
                    rec["memory"].get("temp_size", 0)
                print(f"  ok: lower {rec['lower_s']}s compile "
                      f"{rec['compile_s']}s, args+temp/device "
                      f"{per_dev/2**30:.2f} GiB, flops/dev "
                      f"{rec['flops']:.3g}, coll/dev "
                      f"{rec['collectives']['total_bytes']/2**20:.1f} MiB",
                      flush=True)
            except Exception as e:              # noqa: BLE001
                failures.append((tag, repr(e)))
                print(f"  FAIL: {e}\n{traceback.format_exc()}", flush=True)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for tag, err in failures:
            print(f"  {tag}: {err[:200]}")
        raise SystemExit(1)
    print("\nall dry-run cells compiled OK")


if __name__ == "__main__":
    main()
