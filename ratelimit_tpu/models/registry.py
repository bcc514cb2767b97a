"""The limiter-algorithm table: name -> kernel factory + metadata.

The reproduction historically evaluated exactly one policy — the
fixed-window INCR+EXPIRE analog (models/fixed_window.py).  Fixed
windows admit up to 2x the configured rate at a window boundary (the
tail of one window plus the head of the next land inside any
straddling interval); production limiters smooth that with either
two-window interpolation ("sliding window", the CDN-scale estimator)
or GCRA's virtual-scheduling formulation (token bucket as a
theoretical-arrival-time).  This module is the pluggable seam: config
rules carry an ``algorithm:`` field (config/loader.py validates it
against this table), the resolution cache stamps the algorithm onto
each ResolvedRule, and the backend routes each algorithm's
lanes to a dedicated engine bank whose model this table builds.

IMPORT DISCIPLINE: this module must stay importable WITHOUT jax — the
config loader and the offline config_check CLI validate algorithm
names, and they must not drag the device stack in.  Model classes are
imported lazily inside the factory functions.

Rollout contract (docs/ALGORITHMS.md): a new algorithm ships behind
``shadow: true`` first — the rule keeps enforcing fixed-window while
the candidate kernel runs on the same traffic and decision divergence
is counted on /metrics (``ratelimit.tpu.shadow.<algo>.{agree,diverge}``)
and stamped into flight-recorder records.  Enforcement flips per-rule
(drop ``shadow: true``) only after shadow data exists.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Tuple

ALGO_FIXED_WINDOW = "fixed_window"
ALGO_SLIDING_WINDOW = "sliding_window"
ALGO_GCRA = "gcra"

DEFAULT_ALGORITHM = ALGO_FIXED_WINDOW


@dataclass(frozen=True)
class AlgorithmSpec:
    """One pluggable limiter algorithm.

    ``algo_id`` is the small stable integer stamped into flight-
    recorder records (observability/flight.py) — append-only, never
    renumber.  ``windowed_keys`` says whether the cache key embeds the
    window start (fixed windows expire by re-keying every window) or
    is the stable stem (stateful kernels carry their own window/TAT
    per slot and need the slot to SURVIVE rollovers — their engine
    banks run the Python slot table with refresh-on-touch expiry).
    ``state_rows`` documents the per-slot device state layout (the
    checkpoint payload shape).
    """

    name: str
    algo_id: int
    windowed_keys: bool
    state_rows: Tuple[str, ...]
    make_model: Callable  # (num_slots, near_ratio) -> engine model


def _make_fixed_window(num_slots: int, near_ratio: float):
    from .fixed_window import FixedWindowModel

    return FixedWindowModel(num_slots, near_ratio)


def _make_sliding_window(num_slots: int, near_ratio: float):
    from .sliding_window import SlidingWindowModel

    return SlidingWindowModel(num_slots, near_ratio)


def _make_gcra(num_slots: int, near_ratio: float):
    from .gcra import GcraModel

    return GcraModel(num_slots, near_ratio)


ALGORITHMS = {
    ALGO_FIXED_WINDOW: AlgorithmSpec(
        name=ALGO_FIXED_WINDOW,
        algo_id=0,
        windowed_keys=True,
        state_rows=("counts",),
        make_model=_make_fixed_window,
    ),
    ALGO_SLIDING_WINDOW: AlgorithmSpec(
        name=ALGO_SLIDING_WINDOW,
        algo_id=1,
        windowed_keys=False,
        state_rows=("window_start", "curr", "prev"),
        make_model=_make_sliding_window,
    ),
    ALGO_GCRA: AlgorithmSpec(
        name=ALGO_GCRA,
        algo_id=2,
        windowed_keys=False,
        state_rows=("tat_anchor", "tat_cells"),
        make_model=_make_gcra,
    ),
}

#: Loader-facing view: the set of valid ``algorithm:`` values.
ALGORITHM_NAMES = frozenset(ALGORITHMS)

#: flight-recorder id -> name (records carry the id; /debug surfaces
#: resolve it back).
ALGO_ID_TO_NAME = {spec.algo_id: spec.name for spec in ALGORITHMS.values()}


def get_algorithm(name: str) -> AlgorithmSpec:
    spec = ALGORITHMS.get(name)
    if spec is None:
        raise KeyError(
            f"unknown limiter algorithm {name!r} "
            f"(known: {', '.join(sorted(ALGORITHMS))})"
        )
    return spec
