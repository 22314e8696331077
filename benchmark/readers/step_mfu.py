"""The whole step's share of the chips' peak: FLOPs the forward and
backward passes require (benchmark/costs.py, from shapes; recomputation
not counted) x steps / traced window / (chips x peak bf16 FLOP/s)."""

from benchmark import costs


def read(red, facts, peaks, spec):
    steps = facts.get("steps_traced", 0)
    if red is None or steps <= 0 or red.window_ns <= 0:
        return None
    flops = costs.step_flops(facts["n"], facts["e"], facts["widths"],
                             facts["pairs"], facts["use_att"])
    return 100.0 * flops * steps / (red.window_ns * 1e-9) / (
        facts["chips"] * peaks["bf16_flops_per_s"])
