"""Device-plane goodput measurement: one process, the default backend.

Runs ``akka_allreduce_tpu.bench.main`` in this process. It measures the
chip, so it fails (non-zero exit, no JSON row) when JAX finds no TPU; there
is no probe, no second attempt and no CPU row. The per-cell benchmark that
replaces it is ROADMAP S0; ``chip_smoke.py`` is the proof that the system
starts on the chip.
"""

from akka_allreduce_tpu.bench import main

if __name__ == "__main__":
    main()
