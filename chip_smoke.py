#!/usr/bin/env python3
"""Run the JOIN-AGG main path once on a TPU and check its answers.

    python chip_smoke.py

The paper's TPC-H chain query (supplier ⋈ lineitem ⋈ orders ⋈ customer,
grouped by supplier and customer zipcode) goes through ``Q`` → ``Plan``
→ ``JoinAggServer`` on the ``jax`` engine's sparse path, with its
Pallas hop kernels compiled by Mosaic; every answer is compared with the
``tensor`` engine on the same seeded data (see ``repro.launch.smoke``).
It exits non-zero, and prints no ``"ok"``, unless JAX's default device
is a TPU and every check passes.  The last line of its output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The timings it prints are smoke wall times, not benchmark results.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

# lineitem rows of the count / count+sum queries: one size up (160,000)
# the one-hot hop work, growing as n^4, made the smoke take 319 s on one
# v5e (ROADMAP 1.3)
N_MAIN = 100_000
# lineitem rows of the min/max and fused queries: one size up, the fused
# lineitem hop's resident child message exceeds the VMEM bound (VMEM)
N_SMALL = 32_768


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU; JAX's default device is {dev.platform}",
            file=sys.stderr,
        )
        return 1
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.cache import enable_compile_cache
    from repro.launch.smoke import run

    print(f"compile cache: {enable_compile_cache()}")
    run(N_MAIN, N_SMALL, on_chip=True)
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
