"""A traced benchmark run by scope: for each step family of the newest
``--trace 1`` run under ``<directory>/.bench_out/trace/``, every ``dl.*``
scope's time an execution, split into the Q40 kernels and the rest, with the
scope's largest operations (this checkout's ``benchmarks/harness/progtrace.py``
does the reduction; PERF.md section 5's "what a decode step is made of" is
this table). The directory is read for its trace files only: no code of it runs.

    python3 scripts/scope_ops.py [directory, default: this checkout] [operations a scope]
"""
import os
import sys


def main(argv) -> int:
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    root = os.path.abspath(argv[1]) if len(argv) > 1 else here
    top = int(argv[2]) if len(argv) > 2 else 6
    sys.path[:0] = [os.path.join(here, "benchmarks"), here]
    from harness import progtrace

    path = progtrace.newest_trace(root)
    if path is None:
        print(f"no trace under {root}/.bench_out/trace/", file=sys.stderr)
        return 1
    print("trace", path)
    red = progtrace.reduce(progtrace.read(path))
    for fam, d in sorted((red or {}).get("scopes", {}).items()):
        n = d["executions"]
        if not n:
            continue
        print(f"== {fam}: {n} executions, median {d['median_ms']:.3f} ms")
        for scope, ops in sorted(d["ops"].items(), key=lambda kv: -sum(kv[1].values())):
            kern = sum(s for k, s in ops.items() if "_q40_matmul" in k)
            rest = sum(ops.values()) - kern
            print(f"  {scope or 'unscoped':<22} total {1e3 * (kern + rest) / n:8.3f} ms = "
                  f"kernels {1e3 * kern / n:8.3f} + rest {1e3 * rest / n:7.3f}")
            for k, s in sorted(ops.items(), key=lambda kv: -kv[1])[:top]:
                print(f"        {1e3 * s / n:8.3f} ms  {k[:110]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
