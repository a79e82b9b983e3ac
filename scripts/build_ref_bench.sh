#!/bin/sh
# Build the reference-QuEST baseline driver (scripts/ref_bench.c) against
# the unmodified reference sources, CPU multithreaded backend, double
# precision — the configuration bench.py's vs_baseline divides by.
set -e
REF=${REF:-/root/reference}
OUT=${OUT:-/root/repo/.refbuild}
mkdir -p "$OUT"
gcc -O2 -fopenmp -std=c99 -DQuEST_PREC=2 \
    -I"$REF/QuEST/include" -I"$REF/QuEST/src" \
    /root/repo/scripts/ref_bench.c \
    "$REF/QuEST/src/QuEST.c" \
    "$REF/QuEST/src/QuEST_common.c" \
    "$REF/QuEST/src/QuEST_qasm.c" \
    "$REF/QuEST/src/QuEST_validation.c" \
    "$REF/QuEST/src/mt19937ar.c" \
    "$REF/QuEST/src/CPU/QuEST_cpu.c" \
    "$REF/QuEST/src/CPU/QuEST_cpu_local.c" \
    -lm -o "$OUT/ref_bench"
echo "built $OUT/ref_bench"
