"""Synthetic flight tables to the schema of OpenML Airlines_DepDelay_10M,
made from the seed: three calendar columns, two scheduled times written
as hhmm integers, a distance in miles, a carrier and two airports as
level columns, and the departure delay in whole minutes as the target.

The configuration's `schema` block says everything. Per column its name,
feature type and kind:

- `calendar` (Integral): `Month` and `DayofMonth` are one uniform day of
  a 365-day year read through the month lengths, `DayOfWeek` a uniform
  1..7 of its own;
- `hhmm` (Integral): `CRSDepTime` is an hour drawn with the schema's
  `dep_hour_weights` and a uniform minute, written 100 * hour + minute;
  `CRSArrTime` is the departure plus the block time (`block.base_min` +
  distance / `block.miles_per_min`) plus a time-zone shift drawn from
  `block.zone_shifts_h`, modulo a day, written the same way;
- `distance` (Integral): floor of a log-normal (`log_median`,
  `log_sigma`) clipped to [`low`, `high`];
- `level` (PickList): level r of `cardinality` with probability
  proportional to r^-`exponent`; a level's text is `letters` capital
  letters, the rank through a fixed multiplier modulo 26^letters (a
  bijection, so no two ranks share a code).

No cell is missing (the source has none). The target (`target` block):
a row's latent number s = carrier effect + origin effect + an hour
curve rising through the day + a month and a weekday effect + a hub
crossing (the busiest `hub_levels` origins after `hub_hour` get
`hub_evening` more) — the effects of every level come from
`structure_seed`, so every `--seed` draws rows from ONE distribution.
With probability sigmoid(`late_bias` + s) the flight is late: 15 +
floor(exp(N(`late_log_median` + `late_slope` * s, `late_log_sigma`)))
minutes, capped at `cap`; else it leaves round(N(`ontime_mean`,
`ontime_sigma`)) minutes off schedule, clipped to [`ontime_low`, 14].
A whole number of minutes, median near 0, mean near 8, a right tail
past 1,000 and a thin negative side.

numpy only: the program under test receives the finished columns.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from datagen_typed import _seed_seq, _zipf_cdf

MONTH_DAYS = (31, 28, 31, 30, 31, 30, 31, 31, 30, 31, 30, 31)
_MULTIPLIER = {2: 225, 3: 7919}     # coprime with 26^2 and 26^3


def column_names(schema: Dict) -> List[Tuple[str, str]]:
    """[(name, type name)] in the source's column order."""
    return [(c["name"], c["type"]) for c in schema["columns"]]


def level_codes(cardinality: int, letters: int) -> np.ndarray:
    """(cardinality,) object array: the text of ranks 1..cardinality."""
    space = 26 ** letters
    if cardinality > space:
        raise ValueError(f"{cardinality} levels do not fit {letters} letters")
    mixed = (np.arange(1, cardinality + 1) * _MULTIPLIER[letters]) % space
    codes = []
    for v in mixed.tolist():
        text = ""
        for _ in range(letters):
            text = chr(ord("A") + v % 26) + text
            v //= 26
        codes.append(text)
    return np.asarray(codes, object)


def structure(schema: Dict) -> Dict[str, np.ndarray]:
    """The effects behind the target, from the structure seed: one number
    a level of every level column the target reads, one a month, one a
    weekday."""
    st = np.random.default_rng(int(schema["structure_seed"]))
    tg = schema["target"]
    out = {}
    for col in schema["columns"]:
        if col["kind"] == "level" and col["name"] in tg["level_scale"]:
            out[col["name"]] = st.standard_normal(int(col["cardinality"])) \
                * float(tg["level_scale"][col["name"]])
    out["month"] = np.asarray(tg["month_effect"], np.float64)
    out["weekday"] = np.asarray(tg["weekday_effect"], np.float64)
    return out


def _hhmm(minutes: np.ndarray) -> np.ndarray:
    minutes = np.mod(minutes, 1440)
    return (100 * (minutes // 60) + minutes % 60).astype(np.float64)


def make_table(schema: Dict, n_rows: int, seed: int, stream: int = 0
               ) -> Tuple[Dict[str, np.ndarray], np.ndarray]:
    """({column name: (n,) float64 | object of str}, (n,) float64 delay
    in whole minutes)."""
    rng = np.random.default_rng(_seed_seq(seed, stream))
    by_kind = {}
    for col in schema["columns"]:
        by_kind.setdefault(col["kind"], []).append(col)
    made: Dict[str, np.ndarray] = {}
    n = int(n_rows)

    # calendar
    day = rng.integers(0, 365, n)
    starts = np.cumsum((0,) + MONTH_DAYS[:-1])
    month = np.searchsorted(starts, day, side="right")        # 1..12
    made["Month"] = month.astype(np.float64)
    made["DayofMonth"] = (day - starts[month - 1] + 1).astype(np.float64)
    weekday = rng.integers(1, 8, n)
    made["DayOfWeek"] = weekday.astype(np.float64)

    # distance, then the two scheduled times
    dist_col, = by_kind["distance"]
    dist = np.floor(np.exp(float(dist_col["log_median"])
                           + float(dist_col["log_sigma"])
                           * rng.standard_normal(n)))
    dist = np.clip(dist, float(dist_col["low"]), float(dist_col["high"]))
    dep_col = next(c for c in by_kind["hhmm"] if c["name"] == "CRSDepTime")
    weights = np.asarray(dep_col["dep_hour_weights"], np.float64)
    hour = rng.choice(24, size=n, p=weights / weights.sum())
    dep_min = 60 * hour + rng.integers(0, 60, n)
    block = dep_col["block"]
    shift = 60 * rng.choice(np.asarray(block["zone_shifts_h"]), size=n)
    arr_min = dep_min + shift + np.round(
        float(block["base_min"]) + dist / float(block["miles_per_min"])
    ).astype(np.int64)
    made["CRSDepTime"] = _hhmm(dep_min)
    made["CRSArrTime"] = _hhmm(arr_min)
    made[dist_col["name"]] = dist

    # levels
    ranks = {}
    for col in by_kind["level"]:
        card = int(col["cardinality"])
        cdf = _zipf_cdf(card, col["exponent"])
        r = np.minimum(np.searchsorted(cdf, rng.random(n), side="right") + 1,
                       card)
        ranks[col["name"]] = r
        made[col["name"]] = level_codes(card, int(col["letters"]))[r - 1]

    # the target
    tg = schema["target"]
    eff = structure(schema)
    s = eff["month"][month - 1] + eff["weekday"][weekday - 1]
    for name in tg["level_scale"]:
        s = s + eff[name][ranks[name] - 1]
    day_part = np.clip((hour - float(tg["hour_start"]))
                       / (24.0 - float(tg["hour_start"])), 0.0, 1.0)
    s = s + float(tg["hour_scale"]) * day_part ** 2
    hub = (ranks[tg["hub_column"]] <= int(tg["hub_levels"])) \
        & (hour >= int(tg["hub_hour"]))
    s = s + float(tg["hub_evening"]) * hub
    late = rng.random(n) < 1.0 / (1.0 + np.exp(-(float(tg["late_bias"]) + s)))
    late_min = 15.0 + np.floor(np.exp(
        float(tg["late_log_median"]) + float(tg["late_slope"]) * s
        + float(tg["late_log_sigma"]) * rng.standard_normal(n)))
    ontime = np.clip(np.round(float(tg["ontime_mean"]) + float(
        tg["ontime_sigma"]) * rng.standard_normal(n)),
        float(tg["ontime_low"]), 14.0)
    y = np.where(late, np.minimum(late_min, float(tg["cap"])), ontime)
    ordered = {c["name"]: made[c["name"]] for c in schema["columns"]}
    return ordered, y.astype(np.float64)
