"""Dry-run sweep orchestrator: one subprocess per cell (fresh XLA state)."""
import json, os, subprocess, sys, time

ARCHS = ["qwen2_0_5b", "seamless_m4t_medium", "minicpm_2b", "starcoder2_7b",
         "rwkv6_3b", "recurrentgemma_2b", "pixtral_12b", "llama4_scout_17b_16e",
         "llama4_maverick_400b_a17b", "command_r_plus_104b"]
SHAPES = ["decode_32k", "long_500k", "prefill_32k", "train_4k"]

def main():
    multi = "--multi-pod" in sys.argv
    pod = "pod2" if multi else "pod1"
    out = os.path.join(os.path.dirname(__file__), "dryrun")
    # children are CPU-only dry-runs: never let one reach for a chip
    env = {**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"}
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if multi:
        # pod2 is the shardability proof (the roofline table is single-pod
        # per the assignment): compile at opt level 0 to fit wall-clock.
        env["XLA_FLAGS"] = "--xla_backend_optimization_level=0"
    for shape in SHAPES:              # cheap kinds first
        for arch in ARCHS:            # small archs first
            path = os.path.join(out, f"{arch}__{shape}__{pod}__int8.json")
            if os.path.exists(path):
                print("skip", path, flush=True)
                continue
            t0 = time.time()
            cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
                   "--shape", shape, "--out", out]
            if multi:
                cmd.append("--multi-pod")
            r = subprocess.run(cmd, env=env, cwd=repo,
                               capture_output=True, text=True, timeout=7200)
            status = "ok" if r.returncode == 0 else "FAIL"
            print(f"{arch} {shape} {pod}: {status} {time.time()-t0:.0f}s", flush=True)
            if r.returncode != 0:
                with open(path + ".err", "w") as f:
                    f.write(r.stdout[-3000:] + "\n====\n" + r.stderr[-6000:])

if __name__ == "__main__":
    main()
