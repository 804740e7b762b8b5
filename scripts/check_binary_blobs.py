#!/usr/bin/env python3
"""Fail if a large binary is staged for commit.

Raw profiler blobs and similar artifacts belong in artifact storage,
not git: once committed they grow every clone forever. This check walks
the *staged* tree (``git diff --cached``) and fails on any added or
modified file that is binary and larger than the threshold (default
1 MB, override with ``--max-bytes``).

Use as a pre-commit hook or CI step:

    python scripts/check_binary_blobs.py            # staged changes
    python scripts/check_binary_blobs.py --ref HEAD~1   # a commit range
"""

from __future__ import annotations

import argparse
import subprocess
import sys

DEFAULT_MAX_BYTES = 1 << 20  # 1 MB


def _git(*args: str) -> str:
    return subprocess.run(["git", *args], check=True,
                          capture_output=True, text=True).stdout


def staged_paths(ref: str | None) -> list[str]:
    base = ["diff", "--cached"] if ref is None else ["diff", ref]
    out = _git(*base, "--name-only", "--diff-filter=AM", "-z")
    return [p for p in out.split("\0") if p]


def is_binary(path: str) -> bool:
    """Git's own heuristic: a NUL byte in the first block = binary."""
    try:
        blob = subprocess.run(
            ["git", "cat-file", "blob", f":{path}"], check=True,
            capture_output=True).stdout[:8192]
    except subprocess.CalledProcessError:
        # not in the index (e.g. --ref mode): read the worktree
        try:
            with open(path, "rb") as f:
                blob = f.read(8192)
        except OSError:
            return False
    return b"\0" in blob


def staged_size(path: str) -> int:
    try:
        out = subprocess.run(["git", "cat-file", "-s", f":{path}"],
                             check=True, capture_output=True,
                             text=True).stdout
        return int(out.strip())
    except (subprocess.CalledProcessError, ValueError):
        import os

        try:
            return os.path.getsize(path)
        except OSError:
            return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--max-bytes", type=int, default=DEFAULT_MAX_BYTES)
    ap.add_argument("--ref", default=None,
                    help="diff against this ref instead of the index")
    args = ap.parse_args(argv)

    offenders = []
    for path in staged_paths(args.ref):
        size = staged_size(path)
        if size > args.max_bytes and is_binary(path):
            offenders.append((path, size))
    if offenders:
        print("ERROR: large binary files staged for commit "
              f"(limit {args.max_bytes} bytes):", file=sys.stderr)
        for path, size in offenders:
            print(f"  {path}  ({size / 1e6:.1f} MB)", file=sys.stderr)
        print("Raw profiler/trace blobs belong in artifact storage "
              "(chiprun_out/ on a chip run), not in git: the driver "
              "copies three checkouts of this tree.", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
