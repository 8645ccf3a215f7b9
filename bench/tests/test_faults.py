"""A whole run of each cell at test size, with the look for a chip
skipped: a sound run is correct, and each fault that the cell can have,
planted under the timed path, makes ``correct`` come out false.  (The
cells run on one chip, so there is no exchange between chips to leave
out.)"""
import jax.numpy as jnp
import numpy as np

from bench.tests import tiny


def test_serve_sound_run_is_correct():
    res, rec = tiny.run(tiny.SERVE, tiny.serve_cell())
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"]["output_tok_s"]["value"] > 0
    assert rec.notes["served"], "no served request was compared"


def test_serve_altered_token_is_not_correct():
    def plant(eng):
        # every sampled token becomes the one the model likes least
        eng._sample = lambda logits: np.asarray(jnp.argmin(logits, axis=-1))
    res, _ = tiny.run(tiny.SERVE, tiny.serve_cell(), hooks={"engine": plant})
    assert not res["correct"], res["checks"]

