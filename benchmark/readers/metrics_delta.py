"""Reader `metrics_delta`: a ratio of deltas of series on the chip
node's /metrics between two scrapes.

params: {"num": [term, ...], "den": [term, ...] (absent: 1),
         "scale": number (absent: 1), "over": "window" | "trace"}
A term is {"series": name, "labels": {label: value}} or the string
"seconds" (the length of the interval between the two scrapes). A
label value "$primary_method" is the traffic file's primary method.
Nothing to read (a series absent, a zero denominator) -> None.
"""

from lib import scrape


def read(params: dict, ctx):
    m0, m1, seconds = ctx.scrapes(params.get("over", "window"))
    if m0 is None:
        return None

    def term(t):
        if t == "seconds":
            return seconds
        labels = {k: (ctx.primary_method if v == "$primary_method" else v)
                  for k, v in t.get("labels", {}).items()}
        return scrape.delta(m0, m1, t["series"], labels)

    def side(terms):
        vals = [term(t) for t in terms]
        return None if any(v is None for v in vals) else sum(vals)

    num = side(params["num"])
    den = side(params["den"]) if "den" in params else 1.0
    if num is None or not den:
        return None
    return params.get("scale", 1.0) * num / den
