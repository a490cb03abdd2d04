#!/usr/bin/env python
"""One-command multichip parity gate: run ``dryrun_multichip(8)`` on the
8-virtual-device CPU mesh in a child process and print the result as one
JSON line (nothing is written into the tree).

The dryrun asserts the SERVING path on a (dp, tp, sp, ep) mesh is
stream-identical to the mesh-free engine — scheduler decode, chunked
prefill, speculative verify, multi-step, prefix cache, and the
async stack under churn: pipelined decode + fused admissions with zero
pipeline flushes. Invoked by ``make dryrun``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_DEVICES = 8


def main() -> int:
    env = dict(os.environ, GRAFT_SMALL="1", JAX_PLATFORMS="cpu")
    code = (
        f"import sys; sys.path.insert(0, {ROOT!r}); "
        f"from __graft_entry__ import dryrun_multichip; "
        f"dryrun_multichip({N_DEVICES})"
    )
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, env=env, timeout=1800,
        )
        rc, out, err = proc.returncode, proc.stdout, proc.stderr
    except subprocess.TimeoutExpired as e:
        rc = 124

        def _txt(x):
            return x.decode(errors="replace") if isinstance(x, bytes) else (x or "")

        out = _txt(e.stdout)
        # keep the child's stderr tail: a wedged mesh prints its last
        # assert/progress there
        err = _txt(e.stderr)[-1200:] + "\ntimeout after 1800s"
    lines = [ln for ln in out.strip().splitlines() if ln.strip()]
    tail = (lines[-1] + "\n") if lines else ""
    ok = rc == 0 and tail.startswith("dryrun_multichip OK")
    print(json.dumps({
        "n_devices": N_DEVICES,
        "rc": rc,
        "ok": ok,
        "tail": tail if ok else (tail + err[-1500:]),
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
