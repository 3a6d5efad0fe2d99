"""End-to-end driver (the paper's kind: query serving): batched queries on a
partitioned graph with all three engines and the paper's metrics, served
through one GraphSession (shared partition cache, cold/warm load split).

    PYTHONPATH=src python examples/serve_queries.py
    PYTHONPATH=src python examples/serve_queries.py --engine traditional -p 4
    PYTHONPATH=src python examples/serve_queries.py --cache-parts 2 \
        --max-answers 5 --json report.json
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python examples/serve_queries.py --engine mapreduce

The last line is the CPU form of MapReduceMP (four virtual devices); on a
host with four TPU chips drop both variables.

Delegates to repro.launch.serve (the real launcher) with demo defaults.
"""
import sys
sys.path.insert(0, "src")

if __name__ == "__main__":
    from repro.launch.serve import main
    if len(sys.argv) == 1:
        sys.argv += ["--dataset", "synthetic", "--scale", "1.0", "--k", "4",
                     "--scheme", "ecosocial", "--engine", "opat",
                     "--heuristic", "max-sn", "--verify"]
    # map -p to --processors for convenience
    sys.argv = [a if a != "-p" else "--processors" for a in sys.argv]
    main()
