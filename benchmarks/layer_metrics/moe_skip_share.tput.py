"""How often the router's ONE choice is no expert: the token-layer pairs
routed to the last choice (`moe_pairs_skipped`) over all pairs (those
and `moe_pairs_held`: every expert is held here, so a pair is either),
summed over the decode ticks and the admissions of the window of offered
load, in per cent; from the engine's loop ring. A tick routes every slot,
a dead slot's token too. None against a program whose ring lacks the
counter."""
from benchmarks.harness.loop_records import admissions, decoding


def read(obs):
    met = [r for r in decoding(obs) + admissions(obs)
           if "moe_pairs_skipped" in r]
    pairs = sum(r["moe_pairs_skipped"] + r["moe_pairs_held"] for r in met)
    if not pairs:
        return None
    return 100.0 * sum(r["moe_pairs_skipped"] for r in met) / pairs
