/* Reference-QuEST baseline driver for bench.py vs_baseline.
 *
 * Replicates the bench.py workload shape exactly: N-qubit state-vector,
 * DEPTH layers of (N single-qubit unitaries + brick-wall CNOT ladder),
 * then calcProbOfOutcome — run against the UNMODIFIED reference QuEST
 * sources (/root/reference), CPU multithreaded backend, double precision.
 *
 * Build (see scripts/build_ref_bench.sh):
 *   gcc -O2 -fopenmp -std=c99 -I$REF/QuEST/include -I$REF/QuEST/src \
 *       scripts/ref_bench.c $REF/QuEST/src/QuEST.c ... -lm -o .refbuild/ref_bench
 */
#include <math.h>
#include <stdio.h>
#include <stdlib.h>
#include <time.h>

#include "QuEST.h"

static double now_sec(void) {
    struct timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    return ts.tv_sec + 1e-9 * ts.tv_nsec;
}

int main(int argc, char** argv) {
    int n = argc > 1 ? atoi(argv[1]) : 26;
    int depth = argc > 2 ? atoi(argv[2]) : 20;

    QuESTEnv env = createQuESTEnv();
    Qureg q = createQureg(n, env);

    /* one arbitrary fixed 1q unitary (values don't affect the rate);
       QuEST validates unitarity, so build exactly:
       U = [[a, -conj(b)], [b, conj(a)]], |a|^2+|b|^2 = 1 */
    ComplexMatrix2 u;
    double ar = 0.6, ai = 0.3, br = 0.64807406984, bi = 0.35;
    double norm = sqrt(ar*ar + ai*ai + br*br + bi*bi);
    ar /= norm; ai /= norm; br /= norm; bi /= norm;
    u.real[0][0] = ar;  u.imag[0][0] = ai;
    u.real[0][1] = -br; u.imag[0][1] = bi;
    u.real[1][0] = br;  u.imag[1][0] = bi;
    u.real[1][1] = ar;  u.imag[1][1] = -ai;

    initZeroState(q);
    long gates = 0;
    double t0 = now_sec();
    for (int d = 0; d < depth; ++d) {
        for (int t = 0; t < n; ++t) {
            unitary(q, t, u);
            ++gates;
        }
        for (int t = d % 2; t < n - 1; t += 2) {
            controlledNot(q, t, t + 1);
            ++gates;
        }
    }
    qreal prob = calcProbOfOutcome(q, n - 1, 0);
    double dt = now_sec() - t0;

    double amps = (double)gates * pow(2.0, n);
    printf("{\"n\": %d, \"depth\": %d, \"gates\": %ld, \"seconds\": %.3f, "
           "\"amp_updates_per_sec\": %.4g, \"prob\": %.6f}\n",
           n, depth, gates, dt, amps / dt, (double)prob);

    destroyQureg(q, env);
    destroyQuESTEnv(env);
    return 0;
}
