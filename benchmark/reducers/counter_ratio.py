"""``scale`` times a product of counts over a product of counts.  Each name
in ``num`` and ``den`` is looked up among the runner's counters first and in
the configuration second (``n_routed_experts``: the experts held;
``num_experts_per_tok``).  None when a counter is missing - a runner or a
program that does not count it - or the denominator is 0.

With ``runners/serve_hybrid.py``'s counters (sums over the decode steps of
the expert layers):

- ``experts_touched.serve`` = 100 ``moe_touched`` / (``moe_steps`` x held):
  the mean share of held experts a step gives a token;
- ``expert_load_max_over_mean.serve`` = ``moe_max_load`` x held /
  ``moe_pairs``: the mean largest load of one expert over the mean load, the
  straggler a grouped product waits for;
- ``pairs_here.serve`` = 100 ``moe_pairs`` / (``moe_tokens`` x top-k): the
  share of a token's choices that land on held experts."""

import math


def reduce(rc, *, num: list, den: list, scale: float = 1.0):
    def product(names):
        values = [rc.counters.get(n, rc.config.get(n)) for n in names]
        return None if any(v is None for v in values) else math.prod(values)

    top, bottom = product(num), product(den)
    if top is None or not bottom:
        return None
    return scale * top / bottom
