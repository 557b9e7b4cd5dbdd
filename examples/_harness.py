"""Shared scaffolding for the synthetic-benchmark examples.

Holds the pieces every example duplicates: virtual-CPU-mesh setup (the
``--cpu-devices N`` dance that must happen before jax initialises), the
compile-then-timed-loop, and throughput reporting.  Importable as a sibling
module because each example puts its own directory on ``sys.path``.
"""

import sys
import time


def setup_devices(cpu_devices: int) -> None:
    """Force N virtual CPU devices.  Must run before first jax device use."""
    if cpu_devices:
        from horovod_tpu.utils.platform import force_host_device_count
        force_host_device_count(cpu_devices, cpu=True, exact=True)


def require_tpu(flag: str, cpu_devices: int) -> None:
    """Stop when a TPU-sized config (``flag``) finds no TPU and the CPU
    was not asked for with ``--cpu-devices``."""
    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu" and not cpu_devices:
        sys.exit(f"{flag} is a TPU-sized config and jax found no TPU "
                 f"(platform {platform!r}); pass --cpu-devices N to run "
                 "it on the CPU on purpose")


def timed_training(step, params, opt_state, data, steps: int,
                   rank: int, items_per_step: int, unit: str = "sequences"):
    """Compile once, run a timed loop with no host syncs, report throughput.

    ``step(params, opt_state, data) -> (params, opt_state, loss)``.
    Returns the final (params, opt_state).
    """
    params, opt_state, loss = step(params, opt_state, data)  # compile
    float(loss)  # host fetch of the loss: a fence, like block_until_ready
    WARM = 5  # warm window before the timed one
    for _ in range(WARM):
        params, opt_state, loss = step(params, opt_state, data)
    float(loss)
    t0 = time.perf_counter()
    losses = []
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, data)
        losses.append(loss)  # device array; no host sync in the timed loop
    float(loss)  # the last loss depends on every prior step
    dt = time.perf_counter() - t0
    if rank == 0:
        import horovod_tpu as hvd
        # Step indices count TRUE optimizer updates (compile + warm
        # steps precede the timed window), so loss-at-step-N stays
        # comparable across configs.
        for i in range(0, steps, 10):
            print(f"step {i + 1 + WARM:4d} loss {float(losses[i]):.4f}")
        rate = steps * items_per_step / dt
        print(f"{rate:.1f} {unit}/s ({rate / hvd.size():.1f}/chip), "
              f"final loss {float(losses[-1]):.4f}")
    return params, opt_state


def nonlinear_tap(carry, val):
    """Chain ``val`` into ``carry`` through a non-linear full-tensor tap.

    The tap must consume EVERY element of ``val`` NON-LINEARLY: a sliced
    tap lets XLA dead-code the producing op (slice-of-conv ->
    conv-of-slice) and a plain sum lets the algebraic simplifier collapse
    reduce-through-contraction -- both measured producing impossible
    above-peak readings.  A sum of squares survives and fuses with the
    producer's output write.
    """
    import jax.numpy as jnp
    v32 = val.astype(jnp.float32)
    s = jnp.sum(v32 * v32)
    return carry * (1.0 + s * 1e-24).astype(carry.dtype), s


def differential_bench(make_body, example_carry, iters: int,
                       k_spread: int = 256, reps: int = 3):
    """Seconds/op by DIFFERENTIAL timing.

    A dispatch carries a fixed overhead that swamps an op cheaper than
    it, so one scan-chained dispatch of K1 ops and one of K1+k_spread are
    timed (best of ``reps``, fenced by a host fetch of the result) and the
    slope (t2-t1)/(k2-k1) cancels the overhead.
    ``make_body()`` returns a ``lax.scan`` body whose iterations
    data-depend through :func:`nonlinear_tap` so XLA can neither hoist
    nor batch them.  Returns ``(secs_per_op, reliable)`` -- ``reliable``
    is False when the spread is within ~2x the jitter envelope and the
    slope must not be read as a throughput claim.
    """
    import jax
    from jax import lax

    def make(k):
        @jax.jit
        def run(c):
            _o, taps = lax.scan(make_body(), c, None, length=k)
            return taps[-1]
        return run

    k1, k2 = iters, iters + k_spread
    r1, r2 = make(k1), make(k2)

    def timed(fn):
        float(fn(example_carry))          # compile + warm fence
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            float(fn(example_carry))      # host fetch = fence
            best = min(best, time.perf_counter() - t0)
        return best

    t1, t2 = timed(r1), timed(r2)
    secs = max((t2 - t1) / (k2 - k1), 1e-9)
    reliable = (t2 - t1) > 0.2 * t1
    return secs, reliable
