"""Device time a round of the two latent projections of every expert layer
(stream -> latent before the dispatch, latent -> stream after the combine):
the ops under scopes `.../latent_down` and `.../latent_up`, both passes
(scope_math.py). Nothing to read in a program whose experts work in the
stream's own width."""
from __future__ import annotations


def read(run):
    parts = ("/latent_down/", "/latent_up/")
    return run.ctx.load("scope_math.py").sum_ms(
        run, lambda op: any(p in "/" + op["scope"] + "/" for p in parts)) or None
