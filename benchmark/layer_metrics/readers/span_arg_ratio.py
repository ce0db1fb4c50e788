"""The sum of one numeric argument over the sum of another, over the named
spans in the window that carry both (the second above 0)."""


def read(reading, params):
    num, den = params["num"], params["den"]
    spans = reading.spans_in_window(cat=params.get("cat"),
                                    names=(params["name"],))
    pairs = [(s.args[num], s.args[den]) for s in spans
             if s.args and s.args.get(num) is not None and s.args.get(den)]
    total = sum(d for _, d in pairs)
    return sum(n for n, _ in pairs) / total if total else None
