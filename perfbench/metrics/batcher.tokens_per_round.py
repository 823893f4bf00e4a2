"""Tokens appended per decode round, counted where they are made: the sum
of ``tokens`` over the count of ``tdt.batcher.decode_round`` spans
(``batcher.tokens_per_step`` counts the same from outside)."""
from harness import spans as sp

UNIT = "tokens/step"


def read(run):
    spans = sp.of(run)
    rounds = spans.named(sp.ROUND) if spans else []
    if not rounds:
        return None
    return sum(int(s.stats.get("tokens", 0)) for s in rounds) / len(rounds)
