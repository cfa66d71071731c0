"""Read the tiled layouts in a compiled TPU program's text.

``compiled.as_text()`` prints every array with its layout, e.g.
``u8[2048,84,84,4]{3,2,1,0:T(8,128)(4,1)}``: the dimensions, then
``minor_to_major`` (the FIRST index is the minor-most dimension: the one
that lands in a tile's 128 lanes), then the tiles.  ``T(8,128)`` pads the
minor-most dimension to a multiple of 128 and the next one to a multiple
of 8; a further ``(4,1)`` packs four 8-bit rows into one 32-bit sublane
and pads nothing more.  So that array holds 2048 x 84 x 88 x 128 bytes,
1.94 GB, for 57.8 MB of frames, while ``{0,3,2,1:T(4,128)(4,1)}`` (the
2048 in the lanes, the 4 in the sublanes) holds exactly 57.8 MB
(docs/PERFORMANCE.md, "Reading a tiled layout").  jax-free: text in,
numbers out; the layout tests and a builder's scratch scripts share it.
"""

from __future__ import annotations

import math
import re
from typing import Iterable, Iterator, List, NamedTuple, Tuple

_ITEMSIZE = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "s16": 2,
    "u16": 2, "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8,
    "u64": 8, "f64": 8,
}
_ARRAY = re.compile(r"\b([a-z]+\d*[a-z0-9]*)\[([\d,]*)\]\{([\d,]*)(?::([^}]*))?\}")
_TILE = re.compile(r"\(([\d,]+)\)")


class TiledArray(NamedTuple):
    dtype: str
    dims: Tuple[int, ...]
    minor_to_major: Tuple[int, ...]
    tiles: Tuple[Tuple[int, ...], ...]

    @property
    def elements(self) -> int:
        return math.prod(self.dims)

    @property
    def minor_dim(self) -> int:
        """Size of the dimension that lands in the lanes."""
        return self.dims[self.minor_to_major[0]] if self.dims else 1

    @property
    def logical_bytes(self) -> int:
        return self.elements * _ITEMSIZE[self.dtype]

    @property
    def physical_bytes(self) -> int:
        """Bytes as stored: each tile in turn pads the trailing dimensions
        of the (already tiled) major-to-minor shape to whole tiles."""
        shape: List[int] = [self.dims[i] for i in reversed(self.minor_to_major)]
        for tile in self.tiles:
            k = len(tile)
            lead, tail = shape[: len(shape) - k], shape[len(shape) - k:]
            tail = [1] * (k - len(tail)) + tail
            shape = lead + [-(-d // t) for d, t in zip(tail, tile)] + list(tile)
        return math.prod(shape) * _ITEMSIZE[self.dtype]

    @property
    def padding(self) -> float:
        """Stored bytes over the bytes of its elements (1.0 is dense)."""
        return self.physical_bytes / max(self.logical_bytes, 1)


def parse_array(text: str) -> TiledArray:
    """One ``dtype[dims]{minor_to_major:tiles}`` as the compiler prints it."""
    m = _ARRAY.search(text)
    if m is None or m.group(1) not in _ITEMSIZE:
        raise ValueError(f"no tiled array in {text!r}")
    return _from_match(m)


def _from_match(m: re.Match) -> TiledArray:
    ints = lambda s: tuple(int(x) for x in s.split(",") if x)  # noqa: E731
    annotations = m.group(4) or ""
    tiles = ()
    if annotations.startswith("T"):
        # T(8,128)(4,1)S(1): the tiles end where another letter begins
        run = re.match(r"T((?:\([\d,]+\))+)", annotations)
        tiles = tuple(ints(t) for t in _TILE.findall(run.group(1)))
    return TiledArray(m.group(1), ints(m.group(2)), ints(m.group(3)), tiles)


def arrays(text: str, dtype: str) -> Iterator[TiledArray]:
    """Every distinct array of ``dtype`` that ``text`` names."""
    seen = set()
    for m in _ARRAY.finditer(text):
        if m.group(1) == dtype and m.group(0) not in seen:
            seen.add(m.group(0))
            yield _from_match(m)


def loop_body_copies(text: str, dtype: str, min_elements: int) -> List[str]:
    """The ``copy``/``transpose`` instructions (and fusions the compiler
    named after one) inside any ``while`` body that produce an array of
    ``dtype`` with at least ``min_elements`` elements: a relayout paid on
    every trip."""
    bodies = set(re.findall(r"\bbody=%?([\w.\-]+)", text))
    found, inside = [], False
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head or line.startswith("}"):
            inside = bool(head) and head.group(1) in bodies
            continue
        if not inside:
            continue
        m = re.match(
            r"\s*(?:ROOT )?%?([\w.\-]+) = (\w+\[[\d,]*\]\{[^}]*\}) ([\w\-]+)\(", line
        )
        if m is None or not m.group(2).startswith(dtype + "["):
            continue
        relayout = m.group(3) in ("copy", "transpose") or (
            m.group(3) == "fusion" and re.match(r"(copy|transpose)", m.group(1))
        )
        if relayout and parse_array(m.group(2)).elements >= min_elements:
            found.append(line.strip()[:200])
    return found


def lane_dense_faults(text: str, dtype: str, min_elements: int, lane_dim: int) -> List[str]:
    """What a program must not hold if its ``dtype`` arrays of at least
    ``min_elements`` elements are stored dense with the ``lane_dim``-sized
    axis in the lanes: an array padded (over 5%) or with another axis
    minor, and a relayout of one on every loop trip.  Empty when clean;
    raises if the program names no such array at all."""
    big = [a for a in arrays(text, dtype) if a.elements >= min_elements]
    if not big:
        raise ValueError(f"the program names no {dtype} array of {min_elements} elements")
    faults = [
        f"{a.padding:.1f}x padded, {a.minor_dim} in the lanes: {a}"
        for a in big if a.padding > 1.05 or a.minor_dim != lane_dim
    ]
    return faults + [
        f"relayout a loop trip: {line}"
        for line in loop_body_copies(text, dtype, min_elements)
    ]


def candidate_state_faults(
    text: str, leaf_shapes: Iterable[Tuple[int, ...]], min_elements: int = 128
) -> List[str]:
    """What a learn step must not hold if it builds no candidate train
    state beside its donated one: a ``conditional`` (a guard choosing
    between two states), and a ``copy`` whose shape is one of
    ``leaf_shapes``, the train state's leaves (the chosen state written
    over the donated buffers, one copy a leaf).  Leaves under
    ``min_elements`` are left out: a counter or a one-element bias the
    compiler may stage in scalar memory, which is no pass over the state."""
    shapes = {tuple(s) for s in leaf_shapes if math.prod(s) >= min_elements}
    faults = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) (conditional|copy)\(", line)
        if m is None:
            continue
        if m.group(2) == "conditional":
            faults.append(f"conditional: {line.strip()[:120]}")
            continue
        array = _ARRAY.match(m.group(1))  # none for a tuple's type
        if array and _from_match(array).dims in shapes:
            faults.append(f"copy of a leaf: {line.strip()[:120]}")
    return faults
