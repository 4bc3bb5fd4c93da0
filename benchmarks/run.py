"""Benchmark driver — one section per paper figure/table.

    PYTHONPATH=src python -m benchmarks.run           # small suite (CI)
    PYTHONPATH=src python -m benchmarks.run --full    # paper-scale suite

Sections:
    fig3  optimization ablations (rel. runtime / rel. modularity)
    fig5  runtime + speedup + modularity vs networkx Louvain
    fig6  phase split / pass split
    fig7  runtime per edge
    fig8  strong scaling (device-count structural scaling)
    dynamic  streaming edge-batch updates/sec vs full recompute
             (+ Pallas batch-apply bit-for-bit gate)
    multistream  batched multi-stream serving vs sequential dynamic
    refine  Leiden-style refinement vs plain Louvain (Q, wall time,
            disconnected-community audit)
    distdyn  sharded streaming updates/sec vs cold sharded recompute
             (in-process on a TPU's chips; a forced-8-device subprocess
             on the CPU)
    fleet  multi-tenant serving fleet (sharded x batched) vs sequential
           per-tenant sharded serving (same placement as distdyn)
    roofline  achieved rates from the committed BENCH_*.json artifacts vs
              the paper's 560M edges/s headline

Every section also writes a machine-readable ``BENCH_<name>.json`` (rows +
wall seconds + backend), so the perf trajectory is diffable across PRs;
``BENCH_OUT_DIR`` redirects the artifacts.  JAX's persistent compile cache
lives where ``JAX_COMPILATION_CACHE_DIR`` says, else in ``<repo>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

# Every section the driver knows, in run order; ``--only`` names must come
# from this list (a typo'd section silently running NOTHING is how perf
# gates rot, so unknown names are a hard error).
SECTIONS = ("fig3", "fig5", "fig6", "fig7", "fig8", "dynamic", "multistream",
            "refine", "distdyn", "fleet", "roofline")


def parse_only(spec: str | None) -> set[str] | None:
    """Validate a ``--only`` spec against ``SECTIONS``.

    Returns the requested subset (None = everything).  Raises ValueError
    naming the unknown entries and the valid set, so the CLI can exit
    non-zero instead of skipping every section.
    """
    if spec is None:
        return None
    names = {s.strip() for s in spec.split(",") if s.strip()}
    unknown = sorted(names - set(SECTIONS))
    if unknown:
        raise ValueError(
            f"unknown section(s) {', '.join(unknown)}; "
            f"valid sections: {', '.join(SECTIONS)}")
    if not names:
        raise ValueError(
            f"--only got no section names; valid sections: "
            f"{', '.join(SECTIONS)}")
    return names


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true",
                    help="paper-scale graphs + 3 repeats (slow)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: " + ",".join(SECTIONS))
    args = ap.parse_args()
    small = not args.full
    repeats = 3 if args.full else 2
    try:
        only = parse_only(args.only)
    except ValueError as exc:
        print(f"error: {exc}", file=sys.stderr)
        sys.exit(2)

    def want(name: str) -> bool:
        return only is None or name in only

    import jax

    from benchmarks.common import emit_json, enable_compile_cache

    enable_compile_cache()
    # A chip belongs to one process: on a TPU every section runs here, on
    # the chips this process holds.
    on_tpu = jax.default_backend() == "tpu"
    t0 = time.perf_counter()
    failed = False

    def section(name: str, title: str, fn) -> None:
        """Run one in-process section and persist its BENCH json."""
        print(f"== {name}: {title} ==")
        t = time.perf_counter()
        rows = fn()
        emit_json(name, rows, seconds=time.perf_counter() - t, small=small)
        print()

    def forced_host_devices(name: str, module: str, title: str) -> bool:
        """Run a sharded section in a subprocess that forces 8 CPU devices
        before JAX initializes (it emits its BENCH json itself)."""
        print(f"== {name}: {title} (8 forced host devices, subprocess) ==")
        env = dict(os.environ)
        env["PYTHONPATH"] = "src" + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        cmd = [sys.executable, "-m", module]
        if not small:
            cmd.append("--full")
        proc = subprocess.run(cmd, env=env)
        if proc.returncode != 0:
            print(f"({name} subprocess failed with code {proc.returncode})")
        print()
        return proc.returncode != 0

    if want("fig3"):
        from benchmarks import bench_fig3_ablations
        section("fig3", "optimization ablations "
                "(relative to the paper's defaults)",
                lambda: bench_fig3_ablations.run(small=small,
                                                 repeats=repeats))
    if want("fig5"):
        from benchmarks import bench_fig5_runtime
        section("fig5", "runtime / speedup / modularity vs networkx",
                lambda: bench_fig5_runtime.run(small=small, repeats=repeats))
    if want("fig6"):
        from benchmarks import bench_fig6_phase_split
        section("fig6", "phase and pass split "
                "(per agg backend x capacity ladder)",
                lambda: bench_fig6_phase_split.run(small=small,
                                                   repeats=repeats))
    if want("fig7"):
        from benchmarks import bench_fig7_edge_factor
        section("fig7", "runtime per edge",
                lambda: bench_fig7_edge_factor.run(small=small,
                                                   repeats=repeats))
    if want("fig8"):
        from benchmarks import bench_fig8_scaling
        section("fig8", "strong scaling (structural, 1..8 devices)",
                lambda: bench_fig8_scaling.run(max_devices=8))
    if want("dynamic"):
        from benchmarks import bench_dynamic
        section("dynamic", "streaming updates/sec vs full recompute "
                "(+ Pallas batch-apply)",
                lambda: bench_dynamic.run(small=small, repeats=repeats))
    if want("multistream"):
        from benchmarks import bench_multistream
        section("multistream",
                "batched multi-stream serving vs sequential dynamic",
                # best-of-5 minimum: the head-to-head is tight enough that
                # 2-vCPU runner noise can flip a low-repeat row.
                lambda: bench_multistream.run(small=small,
                                              repeats=max(repeats, 5)))
    if want("refine"):
        from benchmarks import bench_refine
        section("refine", "Leiden refinement vs plain Louvain "
                "(Q / wall time / connectivity audit)",
                lambda: bench_refine.run(small=small, repeats=repeats))
    if want("distdyn"):
        title = "sharded streaming vs cold sharded recompute"
        if on_tpu:
            from benchmarks import bench_distributed_dynamic
            section("distdyn", f"{title} ({jax.device_count()} chips)",
                    lambda: bench_distributed_dynamic.run(small=small,
                                                          repeats=3))
        else:
            failed |= forced_host_devices(
                "distdyn", "benchmarks.bench_distributed_dynamic", title)
    if want("fleet"):
        title = ("multi-tenant serving fleet vs sequential per-tenant "
                 "sharded serving")
        if on_tpu:
            from benchmarks import bench_fleet
            # best-of-5, as the subprocess entry point runs it.
            section("fleet", f"{title} ({jax.device_count()} chips)",
                    lambda: bench_fleet.run(small=small, repeats=5))
        else:
            failed |= forced_host_devices("fleet", "benchmarks.bench_fleet",
                                          title)
    if want("roofline"):
        # Reads the committed BENCH_*.json artifacts (including any the
        # sections above just refreshed) — raises instead of emitting an
        # empty table when none are found.
        print("== roofline: achieved rates vs the paper's 560M edges/s ==")
        from benchmarks import roofline
        t = time.perf_counter()
        try:
            rows = roofline.run(
                out_dir=os.environ.get("BENCH_OUT_DIR", "."))
            emit_json("roofline", rows, seconds=time.perf_counter() - t)
        except RuntimeError as exc:
            print(f"(roofline failed: {exc})")
            failed = True
        print()
    print(f"benchmarks done in {time.perf_counter() - t0:.1f}s")
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
