"""TPU compute ops for the hot scoring path.

- ``windows``   on-device per-stream ring-buffer window state: the scatter/
  gather core that turns an unordered measurement micro-batch into ordered
  per-series windows for model input.
- ``attention`` fused attention used by the transformer/ViT models.
"""

from sitewhere_tpu.ops.windows import (
    WindowState,
    init_window_state,
    ring_values,
    update_windows,
    gather_windows,
    update_and_gather,
)

__all__ = [
    "WindowState",
    "init_window_state",
    "ring_values",
    "update_windows",
    "gather_windows",
    "update_and_gather",
]
