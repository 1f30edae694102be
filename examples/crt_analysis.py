"""Security analysis walkthrough: the Cardinality Recovery Threshold.

Compares noise strategies on (a) expected filler overhead (performance) and
(b) CRT rounds to recover T (security), then runs the Monte-Carlo attacker
to validate Eq. (1) empirically — the paper's §5.4 in one script.

Run:  PYTHONPATH=src python examples/crt_analysis.py
"""
import jax
import numpy as np

from repro.core.crt import attacker_estimate, crt_rounds, sigma_s2
from repro.core.noise import BetaNoise, ConstantNoise, TruncatedLaplace

N, T = 100_000, 5_000  # oblivious size, true size (T = 5% N)


def main():
    strategies = {
        "tlap narrow (b=2)": TruncatedLaplace(0.5, 5e-5, 1.0),
        "tlap wide (b=2rootN)": TruncatedLaplace(0.5, 5e-5, float(np.sqrt(N))),
        "beta(2,6)": BetaNoise(2, 6),
        "const 10% (caveat!)": ConstantNoise(0.1),
    }
    print(f"N={N}, T={T}; err=+-1 tuple at 99.9% confidence\n")
    print(f"{'strategy':<22}{'addition':<12}{'E[eta]':>10}{'sigma_S^2':>14}{'CRT rounds':>12}")
    for name, s in strategies.items():
        for add in ("sequential", "parallel"):
            r = crt_rounds(s, add, N, T)
            print(
                f"{name:<22}{add:<12}{s.mean(N, T):>10.0f}"
                f"{sigma_s2(s, add, N, T):>14.1f}{r:>12.0f}"
            )
    print(
        "\nTakeaways (paper §5.4): parallel > sequential at equal noise; "
        "Beta-Binomial > TLap; zero-variance strategies are recovered in 1 round."
    )

    # empirical attacker
    noise = TruncatedLaplace(0.5, 5e-5, 10.0)
    for frac in (0.1, 1.0, 4.0):
        r_star = crt_rounds(noise, "sequential", N, T, err=1.0)
        r = max(int(frac * r_star), 1)
        est = attacker_estimate(noise, "sequential", N, T, r, jax.random.PRNGKey(0))
        print(
            f"attacker with r={r:>6} observations ({frac:>3}x CRT): "
            f"T_hat={est['t_hat']:.1f} (true {T}), |err|={est['abs_err']:.2f}"
        )


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
