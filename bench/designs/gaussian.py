"""One dense design of standard normal float32 entries, ``obs`` x ``vars``
from the configuration, keyed ``<config name>-<seed>``; its ``pool``
targets are ``x @ a`` for planted ``a`` with ``|a_j|`` in [1, 2], plus the
configuration's ``target_noise``."""
from bench import data


def make(seed: int, config: dict, pool: int) -> list:
    obs, nvars = int(config["obs"]), int(config["vars"])
    x = data.gaussian(seed, (obs, nvars))
    coefs = data.planted(seed, nvars, pool)
    ys = data.rhs_pool(x, coefs, seed=seed,
                       noise=float(config.get("target_noise", 0.0)))
    return [data.Design(key=f"{config['name']}-{seed}", x=x, ys=ys)]
