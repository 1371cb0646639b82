"""Pool front end (``serve/pool.py``): mean host time of the prep stage
(validate, pack, pad; ``TenantStats.prep_ms``) over the window's epochs."""


def read(obs):
    if not obs.prep_ms:
        return None
    return sum(obs.prep_ms) / len(obs.prep_ms)
