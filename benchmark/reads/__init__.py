"""What a circuit reads back, one module per value of a traffic mix's
``read`` key.  A module gives ``API``, the name of the ``qt`` function
that produces the answer, and ``Read(rng, n, **read_args)`` with

* ``spec(i)``: what circuit ``i`` reads, drawn from ``rng``;
* ``program(qt, qureg, spec)``: the answer through the public API, a
  number or an array of numbers (complex where the API answers complex);
* ``reference(ref, spec)``: the same answer from the family's reference
  object ``ref``.

The first circuit's read is the read's warm-up, so every spec has to run
the program that the first one compiles (an index or a string as a
traced operand, not a static one)."""
