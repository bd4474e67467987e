"""Device time a round of the recomputed forward's products and kernel calls:
`step_recompute_ms`'s ops that hold a `dot` or a `convolution` or are a
`custom-call` -- the part a kept NAME can remove (a block that keeps a
product's result does not make it again). The rest of `step_recompute_ms` is
elementwise and layout work, cheap to make again and dear to keep."""
from __future__ import annotations


def read(run):
    total = run.ctx.load("readers/step_recompute_ms.py")
    return total.made_again(run, total.holds_product)
