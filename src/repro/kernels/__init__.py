"""Pallas TPU kernels for the compute hot-spots.

Each kernel ships as <name>.py (pl.pallas_call + BlockSpec VMEM tiling) with
a pure-jnp oracle in ref.py. Callers pass ``interpret=True`` to run one off
the chip; nothing picks that mode for them. No model path calls these yet:
attention and mLSTM run their XLA formulations (``models/attention.py``,
``models/xlstm.py``).
"""

from .flash_attention import flash_attention
from .mlstm_chunk import mlstm_chunk

__all__ = ["flash_attention", "mlstm_chunk"]
