"""Faults planted under the timed path, each of which the reference
comparison has to catch.  Each takes the admitted tenant."""


def state_unchanged(tenant):
    """The commit returns the store as it was: every epoch's fold lost."""
    store = tenant.session.store

    def commit(*args, **kw):
        store._staged = None
    store.commit = commit


def half_batch(tenant):
    """Half of every batch left out before the device sees it."""
    session = tenant.session
    prepare = session.prepare

    def half(updates, weights=None):
        return prepare({rel: (r[:len(r) // 2], w[:len(w) // 2])
                        for rel, (r, w) in updates.items()})
    session.prepare = half


def answer_altered(tenant):
    """The second epoch's triangle delta off by one where it is made."""
    engine = tenant.session.handles["triangle"].engine
    run = engine.run_delta_plans
    seen = [0]

    def altered(*args, **kw):
        res = run(*args, **kw)
        seen[0] += 1
        if seen[0] == 2:
            res.count_delta += 1
        return res
    engine.run_delta_plans = altered


def exchange_left_out(tenant):
    """The mesh's all_to_all returns what each worker sent: requests are
    answered by their sender, not their owner."""
    import jax
    jax.lax.all_to_all = lambda x, *args, **kw: x


FAULTS = {f.__name__: f for f in (state_unchanged, half_batch,
                                  answer_altered, exchange_left_out)}
