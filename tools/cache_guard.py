#!/usr/bin/env python
"""Run a command with native-flake retries — THE single home of the old
scattered PADDLE_TPU_NO_COMPILE_CACHE retry workarounds.

Semantics (shared by run_tests.sh's serve smoke and the slow smoke test in
tests/test_serving.py):

  * a SIGNAL death (rc >= 128, or a negative subprocess returncode) is the
    known flaky native XLA-CPU tracer crash — retry it;
  * a real failure (0 < rc < 128) propagates immediately;
  * the LAST attempt runs with PADDLE_TPU_NO_COMPILE_CACHE=1 as a
    belt-and-braces fallback (a well-formed cache entry whose AOT code
    the host still cannot run).

Usage:
    python tools/cache_guard.py [--attempts N] [--fresh-dir DIR]... -- cmd...

--fresh-dir DIR is recreated (rm -rf + mkdir) before EVERY attempt so a
command that appends artifacts (e.g. serve_bench --save-programs) never
mixes output from a crashed attempt into a clean one.
"""

from __future__ import annotations

import argparse
import os
import shutil
import subprocess
import sys


def run_guarded(cmd, attempts: int = 3, fresh_dirs=(), env=None) -> int:
    env = dict(os.environ if env is None else env)
    rc = 1
    for attempt in range(1, attempts + 1):
        for d in fresh_dirs:
            shutil.rmtree(d, ignore_errors=True)
            os.makedirs(d, exist_ok=True)
        att_env = dict(env)
        if attempt == attempts and attempts > 1:
            att_env["PADDLE_TPU_NO_COMPILE_CACHE"] = "1"
        rc = subprocess.run(cmd, env=att_env).returncode
        if rc < 0:  # killed by signal: shell-style code for callers
            rc = 128 - rc
        if rc == 0:
            return 0
        if rc < 128:
            return rc  # real failure — never retried
        print(f"cache_guard: attempt {attempt}/{attempts} died with "
              f"rc={rc} (native flake)"
              + (" — final attempt ran cacheless"
                 if attempt == attempts else ", retrying"),
              file=sys.stderr)
    return rc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="retry a command across native-flake signal deaths")
    ap.add_argument("--attempts", type=int, default=3)
    ap.add_argument("--fresh-dir", action="append", default=[],
                    help="recreated before every attempt")
    ap.add_argument("cmd", nargs=argparse.REMAINDER,
                    help="-- command and args")
    args = ap.parse_args(argv)
    cmd = args.cmd
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        ap.error("no command given (use: cache_guard.py [opts] -- cmd...)")
    return run_guarded(cmd, attempts=args.attempts,
                       fresh_dirs=args.fresh_dir)


if __name__ == "__main__":
    sys.exit(main())
