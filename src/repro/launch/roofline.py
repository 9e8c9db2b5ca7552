"""Roofline analysis over the dry-run results (EXPERIMENTS.md §Roofline).

Per (arch × shape × mesh) cell, three per-step time bounds on TPU v5e:

    compute    = dot_flops(per device)            / 197e12  FLOP/s (bf16)
    memory     = hbm_bytes(per device)            / 819e9   B/s
    collective = Σ ring-model traffic per device  / link bandwidth

dot_flops / hbm_bytes come from the trip-count-aware HLO analysis
(hlo_analysis.py; cost_analysis undercounts loop bodies).  Collective
traffic uses ring algorithms: all-gather/all-to-all (k-1)/k × bytes,
all-reduce 2(k-1)/k × bytes, reduce-scatter (k-1) × result bytes,
permute 1×.  Groups that span pods (size 2 / 32 / 512 on the multi-pod
mesh) ride DCN at 25 GB/s; in-pod groups ride ICI at 50 GB/s/link.

MODEL_FLOPS (global, then ÷chips):
    train    6·N_active·D          (D = tokens per step)
    prefill  2·N_active·D
    decode   2·N_active·B + 4·L·nh·hd·S·B   (KV-cache attention reads)
The ratio MODEL/HLO exposes remat recompute, padding waste (uneven head
sharding), and dead flops.
"""
from __future__ import annotations

import argparse
import json
import os

from repro.configs import get_config
from repro.models.config import SHAPES

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9
DCN_BW = 25e9
CHIPS = {"single": 256, "multi": 512}


def ring_traffic(kind: str, nbytes: float, k: int) -> float:
    if k <= 1:
        return 0.0
    if kind == "all-reduce":
        return 2 * (k - 1) / k * nbytes
    if kind == "reduce-scatter":
        return (k - 1) * nbytes  # nbytes = result shard
    if kind == "collective-permute":
        return nbytes
    return (k - 1) / k * nbytes  # all-gather / all-to-all


def collective_seconds(colls: list[dict], mesh_kind: str) -> float:
    total = 0.0
    for c in colls:
        k = max(int(c.get("group", 1)), 1)
        bw = DCN_BW if (mesh_kind == "multi" and k in (2, 32, 512)) else ICI_BW
        total += ring_traffic(c["kind"], c["bytes"], k) / bw
    return total


def model_flops(arch: str, shape_name: str) -> float:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    N = cfg.active_param_count()
    B, S = shape.global_batch, shape.seq_len
    # useful causal attention flops per layer per sequence (fwd):
    # qk + av over the causal half = 2 * (S^2/2) * nh * hd * 2 = 2 S^2 nh hd
    n_attn_layers = 0
    if cfg.family in ("dense", "moe", "vlm"):
        n_attn_layers = cfg.n_layers
    elif cfg.family == "hybrid":
        n_attn_layers = cfg.n_layers // max(cfg.shared_period, 1)
    elif cfg.family == "audio":
        n_attn_layers = cfg.n_layers + cfg.enc_layers  # self (+cross ~small)
    attn_fwd = 2.0 * S * S * cfg.n_heads * cfg.hd * n_attn_layers * B
    if shape.kind == "train":
        return 6.0 * N * B * S + 3.0 * attn_fwd
    if shape.kind == "prefill":
        return 2.0 * N * B * S + attn_fwd
    # decode: one token over a length-S cache
    flops = 2.0 * N * B
    flops += 4.0 * n_attn_layers * cfg.n_heads * cfg.hd * S * B
    return flops


def load_cells(out_dir: str) -> list[dict]:
    cells = []
    for fname in sorted(os.listdir(out_dir)):
        if fname.endswith(".json"):
            with open(os.path.join(out_dir, fname)) as f:
                cells.append(json.load(f))
    return cells


def analyze_cell(rec: dict) -> dict | None:
    if rec.get("status") != "ok":
        return None
    mesh_kind = rec["mesh"]
    chips = CHIPS[mesh_kind]
    compute_t = rec["dot_flops"] / PEAK_FLOPS
    memory_t = rec.get("hbm_bytes", rec.get("bytes_accessed_cost_analysis", 0)) / HBM_BW
    colls = []
    for kind, v in rec.get("collectives_by_kind", {}).items():
        # reconstruct per-kind average group from detail if present
        colls.append({"kind": kind, "bytes": v["bytes"], "group": 16})
    if "collectives_detail" in rec:
        colls = rec["collectives_detail"]
    coll_t = collective_seconds(colls, mesh_kind)
    mf = model_flops(rec["arch"], rec["shape"]) / chips
    terms = {"compute": compute_t, "memory": memory_t, "collective": coll_t}
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    return {
        **rec,
        "compute_s": compute_t,
        "memory_s": memory_t,
        "collective_s": coll_t,
        "dominant": dominant,
        "model_flops_per_chip": mf,
        "useful_ratio": mf / rec["dot_flops"] if rec["dot_flops"] else 0.0,
        "roofline_fraction": compute_t / bound if bound else 0.0,
    }


# ----------------------------------------------------------------------
# fused-hop kernel cost model (DESIGN.md §13)
# ----------------------------------------------------------------------

#: per-core VMEM budget the autotuner keeps a fused-hop cell within
VMEM_BYTES = 16 * 2**20


def _vmem_tile(rows: int, cols: int) -> int:  # tile-math
    """Bytes of a 32-bit ``(rows, cols)`` array in VMEM, padded to the
    ``(8, 128)`` sublane × lane tiling."""
    return 4 * (-(-max(rows, 1) // 8) * 8) * (-(-max(cols, 1) // 128) * 128)


def fused_hop_vmem_bytes(  # tile-math
    block_e: int,
    block_s: int,
    block_r: int,
    child_rows: tuple[int, ...],
    child_widths: tuple[int, ...],
    width: int,
    k: int,
    kind: str = "sum",
) -> int:
    """Bytes of VMEM one fused-hop grid cell holds, padded to the
    ``(8, 128)`` tiling.

    Pipelined windows count twice (double-buffered): the edge tile's
    1-D key/index columns and ``(block_e, k)`` weights, and the output
    tile.  Whole-array windows — the child messages and the product's
    expansion matrices — are single-buffered and count once.  Body
    temporaries count once, at the kernel's ``SUB_E``-edge sub-chunk:
    the gather selector and its iota, each child's gathered tile (twice
    for min/max, which carries a finiteness mask), the widened product
    operands, the scatter selector and its iota; for min/max add the
    whole-tile candidate and key-column scratch plus one
    ``(block_s, 8, width)`` window.  Checked against what the TPU
    compiler allocates for a described v5e: on the tested shapes it
    over-counts, never under-counts."""
    rows_pad = [max(-(-r // block_r) * block_r, block_r) for r in child_rows]
    out_w = max(width * k, 1)
    piped = 4 * block_e * (1 + len(child_rows)) + _vmem_tile(block_e, k)
    piped += _vmem_tile(block_s, out_w)
    resident = sum(
        _vmem_tile(r, wc * k) for r, wc in zip(rows_pad, child_widths)
    )
    wa = 1
    for wc in child_widths:  # expansion matrices (see fused_hop)
        if wc > 1 and wa * k > 1:
            resident += _vmem_tile(wa * k, wa * wc * k)
        if wa > 1:
            resident += _vmem_tile(wc * k, wa * wc * k)
        wa *= wc
    minmax = kind != "sum"
    from repro.kernels.fused_hop import SUB_E

    sub_e = min(block_e, SUB_E)
    temps = 2 * _vmem_tile(sub_e, block_r) + 2 * _vmem_tile(block_s, sub_e)
    temps += sum(
        (2 if minmax else 1) * _vmem_tile(sub_e, wc * k)
        for wc in child_widths
    )
    temps += 3 * _vmem_tile(sub_e, out_w)
    if minmax:
        temps += _vmem_tile(block_e, out_w) + _vmem_tile(block_e, 1)
        temps += 2 * block_s * _vmem_tile(8, out_w)
    return 2 * piped + resident + temps


def fused_hop_cost(  # tile-math
    edges: int,
    child_rows: tuple[int, ...],
    child_widths: tuple[int, ...],
    num_segments: int,
    k: int = 1,
    block_e: int = 1024,
    block_s: int = 128,
    block_r: int = 128,
) -> dict[str, float]:
    """Roofline estimate for one fused hop at the given tile config.

    FLOPs per grid cell: the one-hot gather matmuls
    (``2·block_e·rows_pad_c·width_c·k`` per child — the selector dot
    spans every padded child row) plus the segment scatter
    (``2·block_s·block_e·width·k``).  Cells = s_tiles × e_tiles.  HBM
    bytes: the edge arrays and child messages are re-read once per
    segment tile (the output tile is resident, the inputs stream), the
    output is written once.  Seconds = max(flops/PEAK_FLOPS,
    bytes/HBM_BW) — the standard two-term roofline.
    """
    width = 1
    for wc in child_widths:
        width *= wc
    e_tiles = max(-(-edges // block_e), 1)
    s_tiles = max(-(-num_segments // block_s), 1)
    rows_pad = [max(-(-r // block_r) * block_r, block_r) for r in child_rows]
    gather_flops = sum(
        2.0 * block_e * rp * wc * k for rp, wc in zip(rows_pad, child_widths)
    )
    scatter_flops = 2.0 * block_s * block_e * width * k
    flops = (gather_flops + scatter_flops) * e_tiles * s_tiles

    edge_bytes = 4.0 * block_e * e_tiles * (2 + len(child_rows) + k)
    msg_bytes = 4.0 * sum(
        rp * wc * k for rp, wc in zip(rows_pad, child_widths)
    )
    hbm = (edge_bytes + msg_bytes) * s_tiles + 4.0 * s_tiles * block_s * width * k
    seconds = max(flops / PEAK_FLOPS, hbm / HBM_BW)
    return {"flops": flops, "hbm_bytes": hbm, "seconds": seconds}


def markdown_table(cells: list[dict]) -> str:
    rows = [
        "| arch | shape | mesh | compute (ms) | memory (ms) | collective (ms) "
        "| bottleneck | MODEL/HLO | roofline frac | HBM fit |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for rec in cells:
        if rec.get("status") == "skipped":
            rows.append(
                f"| {rec['arch']} | {rec['shape']} | {rec['mesh']} | — | — | — | "
                f"skipped | — | — | {rec['reason']} |"
            )
            continue
        a = analyze_cell(rec)
        if a is None:
            rows.append(
                f"| {rec['arch']} | {rec['shape']} | {rec['mesh']} | — | — | — | "
                f"ERROR | — | — | {rec.get('error','?')[:60]} |"
            )
            continue
        temp = rec.get("memory", {}).get("temp_size_in_bytes", 0)
        fit = f"{temp/2**30:.1f} GiB {'✓' if temp < 14e9 else '✗ OOM'}"
        rows.append(
            f"| {a['arch']} | {a['shape']} | {a['mesh']} "
            f"| {a['compute_s']*1e3:.2f} | {a['memory_s']*1e3:.2f} "
            f"| {a['collective_s']*1e3:.2f} | {a['dominant']} "
            f"| {a['useful_ratio']:.2f} | {a['roofline_fraction']:.2f} | {fit} |"
        )
    return "\n".join(rows)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--results", default="launch_results")
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args()
    cells = load_cells(args.results)
    print(markdown_table(cells))
    if args.json_out:
        out = [analyze_cell(c) or c for c in cells]
        with open(args.json_out, "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
