"""Faults planted in the served search underneath the harness, for the
tests that see ``correct`` turn false. Each ``alter(result, queries)``
gets the device result of one segment's search and returns what is
served in its place; ``patch`` puts it under both serving paths. Named
in ``FAULTS`` so that a test in another process can ask for one."""
import numpy as np


def patch(setattr_, alter) -> None:
    """Break the served search: ``alter`` sees every ``device_anns``
    result, the segment server's (``coordinator.device_anns``) and the
    mesh router's (which takes ``device_search.device_anns`` when it
    builds its step, so this runs before the router serves).
    ``setattr_`` is ``monkeypatch.setattr`` or ``setattr``."""
    from repro.core import device_search
    from repro.serving import coordinator
    real = device_search.device_anns

    def broken(ds, queries, p, **kw):
        return alter(real(ds, queries, p, **kw), queries)

    setattr_(coordinator, "device_anns", broken)
    setattr_(device_search, "device_anns", broken)


def state_unchanged(r, q):
    # the loop's carried results as it started: nothing found
    return r._replace(ids=r.ids * 0 - 1, dists=r.dists * 0 + np.inf)


def half_batch(r, q):
    # only the first half of the batch searched; the rest get its rows
    half = r.ids.shape[0] // 2
    idx = np.arange(r.ids.shape[0]) % max(half, 1)
    return r._replace(ids=r.ids[idx], dists=r.dists[idx])


def answer_altered(r, q):
    # one served id replaced by its neighbour in id order
    return r._replace(ids=r.ids.at[0, 0].set(r.ids[0, 0] + 1))


def answers_altered(r, q):
    # every served id replaced by its neighbour in id order
    return r._replace(ids=r.ids + (r.ids >= 0))


def on_rank(fault, rank: int = 1):
    """``fault`` on one mesh rank only: the other ranks serve sound
    answers (for a search under the router's ``shard_map``)."""
    def alter(r, q):
        import jax
        import jax.numpy as jnp
        bad = fault(r, q)
        hit = jax.lax.axis_index("model") == rank
        return r._replace(ids=jnp.where(hit, bad.ids, r.ids),
                          dists=jnp.where(hit, bad.dists, r.dists))
    return alter


def exchange_left_out(setattr_) -> None:
    """The exchange between chips left out: each rank's all-gather
    returns its own results in every rank's place, so each merges only
    what it found itself."""
    import jax
    import jax.numpy as jnp

    def gather_own(x, axis_name, **kw):
        return jnp.broadcast_to(x[None], (jax.lax.axis_size(axis_name),)
                                + x.shape)

    setattr_(jax.lax, "all_gather", gather_own)


FAULTS = {
    "state_unchanged": state_unchanged,
    "half_batch": half_batch,
    "answer_altered": answer_altered,
    "state_unchanged_on_rank": on_rank(state_unchanged),
    "answers_altered_on_rank": on_rank(answers_altered),
}


def plant(name: str, setattr_=setattr) -> None:
    """Plant the fault ``name``: one of ``FAULTS``, or
    ``exchange_left_out``."""
    if name == "exchange_left_out":
        exchange_left_out(setattr_)
    else:
        patch(setattr_, FAULTS[name])
