"""Small-size files of the benchmark's cells, for runs on the CPU."""
import json
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]


def _load(*parts):
    return json.loads(HERE.joinpath(*parts).read_text())


def server_files():
    cfg = _load("configs", "whisper-tiny.json")
    cfg = {k: cfg[k] for k in ("name", "builder", "arch")}
    cfg["smoke"] = True                        # whisper-tiny's smoke widths
    return {"config": cfg, "traffic": _load("traffic", "server-f32.json"),
            "limits": _load("limits", "server-f32.whisper-tiny.json")}


def loop_files():
    cfg = _load("configs", "resnet18-cifar10.json")
    cfg.pop("packed_params")
    cfg.update(model="resnet10", width=16, stage_sizes=[1, 1, 1, 1])
    tr = _load("traffic", "loop-paper.json")
    tr["fl"].update(n_clients=4, concurrency=2, buffer_size=2,
                    local_epochs=2)
    tr["data"].update(n_train=160, n_test=32)
    return {"config": cfg, "traffic": tr,
            "limits": _load("limits", "loop.resnet18-cifar10.json")}
