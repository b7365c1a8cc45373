"""Roofline analysis over the dry-run artifacts (EXPERIMENTS.md §Roofline).

Three terms per (arch x shape) cell on the single-pod production mesh
(TPU v5e: 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI):

  compute    = HLO_dot_FLOPs/device / peak_FLOPs
  memory     = 2 x materialised-output bytes/device / HBM_bw
               (each buffer is written once and read at least once; fusion
               internals excluded — see launch/hlo_cost.py)
  collective = collective bytes/device / ICI link bw

plus MODEL_FLOPS = 6·N_active·D (train) or 2·N_active·D (inference), the
MODEL/HLO ratio (remat + dispatch + padding waste), and an MFU-style
roofline fraction:  (MODEL_FLOPS time) / max(term)  — i.e. useful compute
time over the best-overlap step time.
"""
from __future__ import annotations

import glob
import json
import os

from repro.configs import SHAPES, get_config
from repro.launch.mesh import DEVICE_PEAKS

# the dry run compiles for the production mesh of TPU v5e chips
_V5E = DEVICE_PEAKS["TPU v5 lite"]
PEAK_FLOPS_BF16, HBM_BW, ICI_BW = (_V5E["flops_bf16"], _V5E["hbm_bw"],
                                   _V5E["ici_bw"])

RESULTS = os.path.join(os.path.dirname(__file__), "results", "dryrun")


def model_flops_per_device(arch: str, shape_name: str, n_devices: int):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        total = 6 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        total = 2 * n_active * tokens
    else:  # decode: one token per sequence
        total = 2 * n_active * shape.global_batch
    return total / n_devices


def load_cells(mesh_tag="pod16x16"):
    cells = {}
    for f in glob.glob(os.path.join(RESULTS, f"*__{mesh_tag}.json")):
        rec = json.load(open(f))
        arch, shape, _ = os.path.basename(f).split("__")
        cells[(arch, shape)] = rec
    return cells


def roofline_row(arch, shape, rec):
    h = rec.get("hlo_cost", {})
    n_dev = rec["n_devices"]
    flops = h.get("flops", 0.0)
    hbm = 2.0 * h.get("hbm_bytes", 0.0)
    coll = h.get("coll_total_bytes", 0.0)
    t_c = flops / PEAK_FLOPS_BF16
    t_m = hbm / HBM_BW
    t_x = coll / ICI_BW
    terms = {"compute": t_c, "memory": t_m, "collective": t_x}
    dom = max(terms, key=terms.get)
    row = {
        "arch": arch, "shape": shape,
        "compute_s": t_c, "memory_s": t_m, "collective_s": t_x,
        "dominant": dom,
        "mem_gib_per_dev": rec["memory"].get("total_bytes_per_device", 0) / 2**30,
        "fits_16g": rec["memory"].get("total_bytes_per_device", 1 << 62) < 16 * 2**30,
    }
    if shape in SHAPES:
        mf = model_flops_per_device(arch, shape, n_dev)
        row["model_flops_dev"] = mf
        row["model_hlo_ratio"] = mf / flops if flops else 0.0
        step = max(terms.values()) or 1e-30
        row["roofline_mfu"] = (mf / PEAK_FLOPS_BF16) / step
    return row


RECOMMEND = {
    "compute": "reduce recompute (remat policy) / pad waste; MXU-align tiles",
    "memory": "fuse elementwise chains; larger tiles; bf16 intermediates",
    "collective": "reshard to cut all-gathers; overlap collectives with "
                  "compute; microbatch to amortise FSDP gathers",
}


def table(mesh_tag="pod16x16"):
    cells = load_cells(mesh_tag)
    rows = [roofline_row(a, s, r) for (a, s), r in sorted(cells.items())]
    return rows


def render_markdown(rows):
    out = ["| arch | shape | compute (s) | memory (s) | collective (s) | "
           "dominant | mem GiB/dev | MODEL/HLO | roofline MFU |",
           "|---|---|---|---|---|---|---|---|---|"]
    for r in rows:
        mfu = f"{r.get('roofline_mfu', 0):.3f}" if "roofline_mfu" in r else "-"
        ratio = f"{r.get('model_hlo_ratio', 0):.2f}" if "model_hlo_ratio" in r else "-"
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.4f} | "
            f"{r['memory_s']:.4f} | {r['collective_s']:.4f} | {r['dominant']} | "
            f"{r['mem_gib_per_dev']:.2f} | {ratio} | {mfu} |")
    return "\n".join(out)


def csv_rows(mesh_tag="pod16x16"):
    rows = table(mesh_tag)
    out = []
    for r in rows:
        name = f"roofline/{r['arch']}/{r['shape']}"
        val = f"{r.get('roofline_mfu', 0):.4f}"
        out.append((name, val,
                    f"dom={r['dominant']};c={r['compute_s']:.4f}s;"
                    f"m={r['memory_s']:.4f}s;x={r['collective_s']:.4f}s;"
                    f"fix={RECOMMEND[r['dominant']]}"))
    return out


if __name__ == "__main__":
    rows = table()
    print(render_markdown(rows))
