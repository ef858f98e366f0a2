#!/usr/bin/env python3
"""Device time of ``train-joyai-flash-8k-1chip``'s step by the program's
own scopes (``self_attn_mla``, ``attn/mla_q``, ``attn/mla_kv``,
``rope/mla``, ``mtp/embed_proj``, ``mtp/block/...``, ``mtp/loss_head``,
``moe/*``, ``loss_head``, ``optimizer``): PERF.md section 5's table for the
cell.  ``scripts/probe_mellum2_scopes.py`` with this cell named and the
prediction block's scopes one level deeper, so that its attention and its
expert layer read apart from its projection and its head.

    chiprun -- python3 scripts/probe_joyai_scopes.py [--steps 6]
        [--top rope/mla]
"""
import sys

import probe_mellum2_scopes

CELL = "train-joyai-flash-8k-1chip"

if __name__ == "__main__":
    given = sys.argv[1:]
    if "--cell" not in given:
        given += ["--cell", CELL]
    if "--depth" not in given:
        given += ["--depth", "4"]
    sys.argv[1:] = given
    probe_mellum2_scopes.main()
