"""Traffic drivers, one per kind of traffic, named by a traffic mix's
``driver`` key. Each offers ``setup(env)``, ``window(state, env)``,
``check(state, env)`` and ``close(state)``."""
