"""The benchmark's own arithmetic on the client's samples.

Percentiles are linear-interpolated order statistics (the definition of
`statistics.quantiles(..., method="inclusive")` and numpy's default), not
nearest-rank: the program's four latency implementations are not used.
"""

from __future__ import annotations


def percentile(values, p: float) -> float:
    """The p-th percentile (0..100) of a non-empty sequence."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no samples")
    k = (len(v) - 1) * p / 100.0
    lo = int(k)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def median(values) -> float:
    return percentile(values, 50.0)


def by_template(samples: list, field: str = "ms") -> dict:
    """{template: [field of each sample]}, in the samples' order."""
    out: dict = {}
    for s in samples:
        out.setdefault(s["template"], []).append(s[field])
    return out


def template_medians(samples: list, field: str = "ms") -> dict:
    return {t: median(v) for t, v in by_template(samples, field).items()}


def slowest_template(samples: list) -> tuple:
    """(template, median) of the template with the largest median."""
    meds = template_medians(samples)
    name = max(meds, key=meds.get)
    return name, meds[name]


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, as the contract measures run-to-run spread."""
    import statistics
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
