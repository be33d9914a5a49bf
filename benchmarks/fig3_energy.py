"""Fig 3: per-node energy per inference cycle, DEFER vs single device
(ResNet50, 4/6/8 nodes)."""
from __future__ import annotations

from benchmarks.common import emit, graph_and_params
from repro.compile_cache import enable_compile_cache
from repro.core.emulator import CodecConfig, emulate


def run(nodes=(4, 6, 8)) -> list[dict]:
    g, _ = graph_and_params("resnet50")
    cfg = CodecConfig(serializer="zfp", compression="none", zfp_rate=16)
    rows = []
    for n in nodes:
        rep = emulate(g, n, cfg)
        rows.append({
            "nodes": n,
            "per_node_energy_j": rep.per_node_energy_j,
            "single_device_energy_j": rep.single_device_energy_j,
            "energy_ratio": rep.energy_ratio,
        })
    return rows


def main() -> None:
    emit("fig3_energy", run())


if __name__ == "__main__":
    enable_compile_cache()
    main()
