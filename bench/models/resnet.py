"""ResNet50 (``models/cnn.py``) as the program builds it."""
from __future__ import annotations

KIND = "oneshot"


def build_graph(cfg: dict):
    from repro.models.cnn import _R50_STAGES, resnet50

    stages = [(b, m, m * cfg["expansion"]) for b, m in
              zip(cfg["stage_blocks"], cfg["bottleneck_widths"])]
    built = [s[:3] for s in _R50_STAGES]
    if stages != built:
        raise ValueError(f"configuration's stages {stages} are not the ones "
                         f"models/cnn.py builds ({built})")
    return resnet50(batch=1, image=cfg["image_size"],
                    num_classes=cfg["num_classes"])


def input_shape(cfg: dict) -> tuple[int, ...]:
    return (1, cfg["image_size"], cfg["image_size"], cfg["in_channels"])
