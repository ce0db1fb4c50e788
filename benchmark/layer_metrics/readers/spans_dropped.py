"""Spans the program's recorder lost: ring overwrites (``dropped``, summed over
every drain so far) plus watched arrays that were gone before the readiness
watcher reached them (``unwatched``). Any count above 0 means the span metrics
of this run read a truncated record."""


def read(reading, params):
    if not reading.spans:               # an untraced run recorded none
        return None
    try:
        from futuresdr_tpu.telemetry import spans
    except ImportError:
        return None
    rec = spans.recorder()
    return float(rec.dropped + getattr(rec, "unwatched", 0))
