"""Beyond-paper: roofline terms per (arch x shape x mesh) from the compiled
multi-pod dry-run (results/dryrun_*.json, produced by repro.launch.dryrun)."""
import json
import os

from bench_lib import emit


def run(results_dir: str = "results"):
    for mesh in ("pod16x16", "pod2x16x16"):
        path = os.path.join(results_dir, f"dryrun_{mesh}.json")
        if not os.path.exists(path):
            emit(f"roofline.{mesh}", 0.0, "status=missing (run repro.launch.dryrun)")
            continue
        with open(path) as f:
            rows = json.load(f)
        for key, v in sorted(rows.items()):
            if v.get("status") != "ok":
                emit(f"roofline.{mesh}.{key}", 0.0, f"status={v.get('status')}")
                continue
            emit(f"roofline.{mesh}.{key}",
                 (v.get("lower_s", 0) + v.get("compile_s", 0)) * 1e6,
                 f"bottleneck={v['bottleneck']};"
                 f"t_compute={v['t_compute_s']:.3g};"
                 f"t_memory={v['t_memory_s']:.3g};"
                 f"t_collective={v['t_collective_s']:.3g};"
                 f"roofline_frac={v['roofline_fraction']:.3f};"
                 f"useful_flop_ratio={v['useful_flop_ratio']:.2f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    run()
