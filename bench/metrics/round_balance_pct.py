"""Share of the chips' sweep steps that merge a frame's own candidates
while each round waits for its slowest chip, as a percentage: over the
window's ``ph.harvest`` spans that carry the round's ``candidates`` (one
count per real frame) and ``chips``, the candidates summed over
``chips`` times each round's largest count.  A round whose chips hold
equal counts reads 100; a part-filled last round reads at most its
share of filled chips."""
from bench import stages


def read(run):
    rounds = [s.attrs for s in stages.window_spans(run)
              if s.name == "ph.harvest" and s.attrs.get("candidates")]
    steps = sum(a["chips"] * max(a["candidates"]) for a in rounds)
    if not steps:
        return None
    return 100.0 * sum(sum(a["candidates"]) for a in rounds) / steps
