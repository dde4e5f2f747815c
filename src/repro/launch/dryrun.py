import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
os.environ["JAX_PLATFORMS"] = "cpu"    # placeholder devices, never a chip

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh and extract memory / cost / roofline terms.

The lines above MUST precede any jax import: jax locks the device count
and the platform at first init, and the dry-run needs 512 placeholder CPU
devices so ``jax.make_mesh`` can build the 2x16x16 production mesh.  It
pins itself to the CPU platform, so on a machine with an accelerator it
never takes the chip from another process.  (Only this module sets the
flag — tests and benches see the real single device.)

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2_0_5b \
        --shape train_4k [--multi-pod] [--policy int8|float32|int8_block]
    PYTHONPATH=src python -m repro.launch.dryrun --all --out experiments/dryrun

Each cell writes a JSON record: per-device memory analysis, HLO FLOPs /
bytes, collective wire bytes by kind, the three roofline terms, the
MODEL_FLOPS/HLO_FLOPs usefulness ratio, and compile wall time.
"""

import argparse
import json
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ARCH_IDS, SHAPES, cell_runnable, get_config
from ..core import NumericPolicy
from ..core.policy import FLOAT32, PAPER_INT8
from ..data import make_batch_specs
from ..models import get_model
from ..runtime.sharding import DEFAULT_RULES, MULTIPOD_RULES, ShardingRules, use_rules
from .mesh import make_production_mesh
from .roofline import model_flops, roofline_from_compiled
from .steps import (TrainHyper, batch_shardings, cache_shardings,
                    cache_template, make_decode_step, make_prefill_step,
                    make_train_step, params_shardings, params_template,
                    state_shardings, train_state_template)

POLICIES = {
    "int8": PAPER_INT8,
    "float32": FLOAT32,
    "int8_block": NumericPolicy(block=128),
}

# gradient-accumulation splits per (arch, train shape): keeps per-device
# activation boundaries inside v5e HBM (validated via memory_analysis)
MICROBATCH: Dict[str, int] = {
    "command_r_plus_104b": 16,
    "starcoder2_7b": 8,
    "qwen2_0_5b": 2,
    "minicpm_2b": 4,
    "rwkv6_3b": 4,
    "pixtral_12b": 8,
    "recurrentgemma_2b": 4,
    "llama4_maverick_400b_a17b": 16,
    "llama4_scout_17b_16e": 8,
    "seamless_m4t_medium": 2,
}


def _rules_for(shape, multi_pod: bool) -> ShardingRules:
    rules = MULTIPOD_RULES if multi_pod else DEFAULT_RULES
    dp = 32 if multi_pod else 16
    if shape.global_batch % dp:
        # batch too small to shard (long_500k b=1): replicate batch axis,
        # parallelism comes from the model axis alone.
        rules = ShardingRules({**rules, "batch": None})
    return rules


def _memory_dict(mem) -> Dict:
    return {
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
        "code_bytes": mem.generated_code_size_in_bytes,
    }


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             policy_name: str = "int8", verbose: bool = True,
             microbatch: Optional[int] = None, rng: str = "threefry2x32",
             fused_proj: bool = False, qflow: bool = False,
             qweights: bool = False, dump_breakdown: bool = True) -> Dict:
    import dataclasses as _dc
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    policy = POLICIES[policy_name]
    if fused_proj:
        policy = _dc.replace(policy, fused_proj=True)
    if qflow and policy.enabled:
        policy = _dc.replace(policy, qflow=True)
    if qweights and policy.enabled:
        policy = _dc.replace(policy, qweights=True)
    if rng == "hash":
        # hash selects the cheap per-element SR stream inside the
        # representation mapping; the key plumbing stays threefry.
        policy = _dc.replace(policy, rng="hash")
        rng = "threefry2x32"
    ok, why = cell_runnable(cfg, shape)
    record = {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
              "policy": policy_name, "rng": rng, "fused_proj": fused_proj,
              "qflow": qflow, "qweights": qweights}
    if not ok:
        record["status"] = why
        return record

    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = _rules_for(shape, multi_pod)
    n_chips = mesh.devices.size
    mod = get_model(cfg)

    from .steps import key_template

    t0 = time.time()
    with use_rules(rules, mesh):
        key_t = key_template(rng)
        if shape.kind == "train":
            mb = microbatch or MICROBATCH.get(arch, 1)
            hyper = TrainHyper(microbatch=mb, rng_impl=rng)
            step = make_train_step(cfg, policy, hyper)
            state_t = train_state_template(cfg, policy)
            state_s = state_shardings(cfg, policy, mesh, rules)
            batch_t = make_batch_specs(cfg, shape)
            batch_s = batch_shardings(cfg, mesh, rules, batch_t)
            lowered = jax.jit(
                step,
                in_shardings=(state_s, batch_s, NamedSharding(mesh, P())),
                out_shardings=(state_s, NamedSharding(mesh, P())),
            ).lower(state_t, batch_t, key_t)
            record["microbatch"] = mb
        elif shape.kind == "prefill":
            step = make_prefill_step(cfg, policy, max_len=shape.seq_len,
                                     rng_impl=rng)
            p_t = params_template(cfg)
            p_s = params_shardings(cfg, mesh, rules)
            batch_t = make_batch_specs(cfg, shape)
            batch_s = batch_shardings(cfg, mesh, rules, batch_t)
            lowered = jax.jit(
                step, in_shardings=(p_s, batch_s, NamedSharding(mesh, P())),
            ).lower(p_t, batch_t, key_t)
        else:  # decode
            step = make_decode_step(cfg, policy, rng_impl=rng)
            p_t = params_template(cfg)
            p_s = params_shardings(cfg, mesh, rules)
            b = shape.global_batch
            cache_t = cache_template(cfg, b, shape.seq_len,
                                     src_len=shape.seq_len)
            cache_s = cache_shardings(cfg, mesh, rules, cache_t)
            tok_t = jax.ShapeDtypeStruct((b,), jnp.int32)
            tok_s = NamedSharding(mesh, rules.spec(("batch",)))
            pos_t = jax.ShapeDtypeStruct((), jnp.int32)
            repl = NamedSharding(mesh, P())
            from .steps import _sanitize_spec
            logit_spec = _sanitize_spec(rules.spec(("batch", "vocab")),
                                        (b, cfg.vocab), mesh)
            lowered = jax.jit(
                step,
                in_shardings=(p_s, cache_s, tok_s, repl, repl),
                out_shardings=(NamedSharding(mesh, logit_spec), cache_s),
            ).lower(p_t, cache_t, tok_t, pos_t, key_t)
        t_lower = time.time() - t0

        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    from .hlo_cost import analyze_hlo
    text = compiled.as_text()
    cost = analyze_hlo(text)
    terms = roofline_from_compiled(compiled, hlo_text=text)
    mf = model_flops(cfg, shape)
    if dump_breakdown:
        record["bytes_by_op_top"] = {k: float(v) for k, v in cost.top_bytes(14).items()}
    record.update({
        "status": "ok",
        "n_chips": n_chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": _memory_dict(mem),
        "roofline": terms.as_dict(),
        "model_flops_total": mf,
        "model_flops_per_chip": mf / n_chips,
        # usefulness: ideal model FLOPs vs compiled FLOPs (per chip both)
        "useful_flop_ratio": (mf / n_chips) / max(terms.flops, 1.0),
    })
    if verbose:
        print(json.dumps(record, indent=2, default=float))
        print(f"memory_analysis: {mem}")
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--policy", default="int8", choices=list(POLICIES))
    ap.add_argument("--microbatch", type=int, default=None)
    ap.add_argument("--rng", default="threefry2x32",
                    choices=["threefry2x32", "unsafe_rbg", "hash"])
    ap.add_argument("--fused-proj", action="store_true")
    ap.add_argument("--qflow", action="store_true")
    ap.add_argument("--qweights", action="store_true")
    ap.add_argument("--tag", default=None, help="suffix for the record file")
    ap.add_argument("--out", default=None, help="directory for JSON records")
    args = ap.parse_args()

    cells_to_run = ([(a, s) for a in ARCH_IDS for s in SHAPES]
                    if args.all else [(args.arch, args.shape)])
    for arch, shape in cells_to_run:
        rec = run_cell(arch, shape, multi_pod=args.multi_pod,
                       policy_name=args.policy, microbatch=args.microbatch,
                       rng=args.rng, fused_proj=args.fused_proj,
                       qflow=args.qflow, qweights=args.qweights)
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            pod = "pod2" if args.multi_pod else "pod1"
            tag = f"__{args.tag}" if args.tag else ""
            path = os.path.join(
                args.out, f"{arch}__{shape}__{pod}__{args.policy}{tag}.json")
            with open(path, "w") as f:
                json.dump(rec, f, indent=2, default=float)
            print(f"wrote {path}")


if __name__ == "__main__":
    main()
