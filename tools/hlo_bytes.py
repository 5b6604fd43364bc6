#!/usr/bin/env python
"""Rank optimized-HLO entry instructions by bytes touched (output+operands).

Usage: python tools/hlo_bytes.py /tmp/rn_hlo.txt [top_n]

Thin CLI wrapper: the parsing and the dtype table live in
paddle_tpu/analysis/hlo_bytes.py — the one source of truth for HLO byte
accounting, shared with analysis/jaxcost.py (static cost model).
Stdlib-only; never imports jax.
"""
from __future__ import annotations

import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# import `analysis` as a top-level package so this loads without
# paddle_tpu/__init__ (which pulls in jax) — then drop the path entry:
# paddle_tpu/ holds Paddle-parity modules (sysconfig.py, ...) that would
# shadow the stdlib for later imports
_PKG_DIR = os.path.join(_REPO, "paddle_tpu")
sys.path.insert(0, _PKG_DIR)
try:
    from analysis.hlo_bytes import (audit_text,  # noqa: E402,F401
                                    allreduce_payload, shape_bytes)
finally:
    sys.path.remove(_PKG_DIR)


def main():
    path = sys.argv[1] if len(sys.argv) > 1 else "/tmp/rn_hlo.txt"
    top_n = int(sys.argv[2]) if len(sys.argv) > 2 else 30
    audit_text(open(path).read(), top_n)


if __name__ == "__main__":
    main()
