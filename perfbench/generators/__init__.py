"""Load generators, one file a kind; a traffic file names its kind.

A generator module has ``build(traffic, env) -> generator``. ``env`` is
the run's ``Env`` (run.py): the system door (``env.sut``), the
configuration dict, ``env.rng(name)`` seeded from ``--seed``,
``env.log`` (the ``ClientLog``), ``env.load_traffic(name)`` for
compositions, and ``env.seconds``. A generator has

    lead_in_s           seconds of traffic to run before the window opens
    drain_s             seconds to wait for stragglers after it closes
    warm()              blocking: touch every shape the window will use
    start(t0)           called at t0 - lead_in_s; begin offering load so
                        that the system is in steady state at t0
    stop(t_end)         the window closed at t_end: stop offering load,
                        wait up to drain_s, cancel what still runs
"""
