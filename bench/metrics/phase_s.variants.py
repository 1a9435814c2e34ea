"""Mean over the jobs submitted in the window of the seconds of their
``variants`` spans: the AutoML engine's host-side data-variant builds, a
part of ``sub_automl`` and ``fine_tune``.  A server that records no such
span gives nothing."""
from stats import done, mean_of


def read(rec):
    return mean_of([sum(s["attrs"]["seconds"] for s in spans) or None
                    for spans in ([s for s in r.get("spans", ())
                                   if s["name"] == "variants"]
                                  for r in done(rec))])
