"""Bytes of device memory the compiled train step takes for its temporaries:
``temp_bytes`` of the program's ``step:build`` span
(``loader_stats["layers"]["train step"]["step:build"]``), which the step reads
from ``memory_analysis()`` of the one program it compiled and runs, once a
compiled batch shape (the mean over the shapes, where a loader's short last
batch compiled a second). The scratch is reserved beside the live buffers
while the step runs and is NOT in ``memory_peak_bytes``; the two together are
what the cell holds of the chip. A program that says no ``temp_bytes``:
nothing to read."""


def read(ctx):
    layers = (ctx["loader_stats"] or {}).get("layers") or {}
    build = (layers.get("train step") or {}).get("step:build") or {}
    temp = (build.get("sum") or {}).get("temp_bytes")
    if not temp or not build.get("spans"):
        return None
    return temp / build["spans"]
