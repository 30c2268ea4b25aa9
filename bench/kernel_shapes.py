"""Shapes as the profiler prints them: ``f32[16,3,32,112]``."""
from typing import List


def dims(shape: str) -> List[int]:
    inner = shape[shape.index("[") + 1:shape.index("]")]
    return [int(d) for d in inner.split(",") if d]


def leading_dim(shape: str) -> int:
    return dims(shape)[0]
