"""What one run recorded: the object every metric reader gets."""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

from chipbench.drive import Window
from chipbench.yardstick import ChipPeaks, Shapes


@dataclasses.dataclass
class Run:
    lanes: int
    chips: int                      # devices the cell serves on
    shapes: Shapes
    peaks: ChipPeaks
    window: Window
    prompt_len: Dict[int, int]      # uid -> prompt tokens
    setup_s: float
    compiles_in_window: int
    memory_peak_bytes: Optional[int]  # the fullest device's peak
    trace: Optional[dict] = None    # chipbench.trace record (--trace 1)

    @property
    def window_s(self) -> float:
        return self.window.t_close - self.window.t_open

    def decode_contexts(self):
        """Context length (keys attended) of every decode token of the
        window: the token of index i of a request with prompt p was
        produced from the query at position p + i - 1 over p + i keys."""
        return [self.prompt_len[uid] + idx
                for u in self.window.units if u.kind == "step"
                for uid, idx in u.tokens]

    def admitted_prompts(self):
        """Prompt lengths of the window's admissions."""
        return [self.prompt_len[uid] for u in self.window.units
                if u.kind == "admit" for uid, _ in u.tokens]
