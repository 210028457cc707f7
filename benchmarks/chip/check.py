"""The comparison that decides ``correct``.

Every kept answer of the window is held to the cell's generator's plain
reference (for field cells, :mod:`.reference`): ``err_<codec>`` is the
worst, over the answers of the arrays stored with that codec
(``refs.codec``), of the answer's worst error over the reference's worst
error on the same values.  Each declared precision is
compared on its own, so that a fault in the 8-bit field cannot hide
behind the 16-bit one.  A run is correct when it kept at least one answer,
no work it issued failed or went unanswered, and each ``err_<codec>`` is
within the limit that the configuration file states
(``check.err_ratio_limit``).

The control puts the reference in the program's place, computed at the
nearest precision below the declared one: 8 bits where 16 are declared,
4 where 8 are.  It has to come out as not correct.
"""
from __future__ import annotations

from typing import Dict, List

from .load import Answer
from .reference import err_ratio

#: the nearest precision below each declared one
LOWER_BITS = {16: 8, 8: 4}


def compare(answers: List[Answer], refs, control=None) -> Dict[str, float]:
    """Worst ``err_ratio`` over ``answers``, per codec; ``refs`` and
    ``control`` are a generator's ``References``, and with ``control`` the
    control's answers (the lower-precision reference) take the program's
    place."""
    worst: Dict[str, float] = {}
    for a in answers:
        codec = refs.codec(a.array)
        truth, ref = refs[a.array].select(a.sel)
        result = a.result if control is None else \
            control[a.array].select(a.sel)[1]
        worst[codec] = max(worst.get(codec, 0.0),
                           err_ratio(result, truth, ref))
    return worst


def decide(err: Dict[str, float], limits: Dict[str, float], checked: int,
           failed: int, unanswered: int) -> Dict[str, dict]:
    """Each number compared, with its limit and whether it held."""
    out = {f"err_{codec}": {"value": err.get(codec, 0.0), "limit": limit,
                            "ok": err.get(codec, 0.0) <= limit}
           for codec, limit in sorted(limits.items())}
    return {
        **out,
        "failed": {"value": failed, "limit": 0, "ok": failed == 0},
        "unanswered": {"value": unanswered, "limit": 0,
                       "ok": unanswered == 0},
        "checked": {"value": checked, "limit": 1, "ok": checked >= 1},
    }
