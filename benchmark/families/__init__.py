"""Circuit families, one module each, found by the configuration's
``family`` key.  A family gives ``Family(cfg)`` with ``n``,
``create(qt, env)`` (the register), ``draw_params(rng)``,
``issue(qt, qureg, params, span)`` (the circuit body),
``snapshot(qureg)`` (the program's state as the reference compares it)
and ``reference(init, chain, on_chip)``, which yields after each circuit
of ``chain`` an object with ``state_err(snapshot)`` and whatever the
mix's read asks of it."""
