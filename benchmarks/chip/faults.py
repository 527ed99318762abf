"""Faults planted under the timed path, each a wrapper of the compiled
step: the check has to call a run with any of them not correct.  The
benchmark's own runs never use them; ``readings.py`` reads them on the chip
and the tests on the CPU."""
import jax
import jax.numpy as jnp


def unchanged(step):
    """The step returns the state it was given."""
    def call(params, opt_state, batch):
        keep = jax.tree.map(jnp.copy, (params, opt_state))
        _, _, met = step(params, opt_state, batch)
        return (*keep, met)
    return call


def half_batch(step):
    """Half of the tokens left out: the loss is the mean over the rest."""
    def call(params, opt_state, batch):
        lab = batch["labels"]
        half = lab.shape[-1] // 2
        batch = dict(batch, labels=lab.at[..., half:].set(-1))
        return step(params, opt_state, batch)
    return call


def altered(step):
    """One answer altered where it is produced: layer 0's second MLP
    matrix moves twice as far as the update says."""
    def call(params, opt_state, batch):
        old = jnp.copy(params["stages"]["mlp"]["w2"][0, 0])
        params, opt_state, met = step(params, opt_state, batch)
        w2 = params["stages"]["mlp"]["w2"]
        params["stages"]["mlp"]["w2"] = w2.at[0, 0].set(2 * w2[0, 0] - old)
        return params, opt_state, met
    return call


FAULTS = {f.__name__: f for f in (unchanged, half_batch, altered)}
