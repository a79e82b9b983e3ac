"""How a circuit's register is prepared, one module per value of a
traffic mix's ``prepare`` key.  A module gives ``Prepare(rng, n)`` with
``spec(i)``, the preparation of circuit ``i`` drawn from ``rng`` as a
tuple whose first entry is the module's name, and
``apply(qt, qureg, spec)``, the preparation through the public API.  The
family's reference starts from the same spec."""
