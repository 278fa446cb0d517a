"""Open-loop request traffic: arrival schedules, Zipf device tags and
the arithmetic of lateness. Arrivals are fixed before the run starts and
do not wait for the server, so a slow server meets a growing queue
instead of a smaller load."""
from __future__ import annotations

import numpy as np


def rng_for(seed: int, salt: int) -> np.random.Generator:
    """A numpy generator for one stream of a run; any non-negative seed,
    however many bits it has."""
    return np.random.default_rng([int(seed), int(salt)])


def poisson_schedule(rate: float, seconds: float,
                     rng: np.random.Generator) -> np.ndarray:
    """Due times, in seconds from the window's start, of Poisson arrivals
    at ``rate`` per second over ``[0, seconds)``, conditioned on their
    count being ``round(rate * seconds)``: given the count, Poisson
    arrival times are independent and uniform. Every seed then offers the
    same number of requests, in another order."""
    if rate <= 0 or seconds <= 0:
        raise ValueError(f"rate and seconds must be > 0: {rate}, {seconds}")
    n = max(1, round(rate * seconds))
    return np.sort(rng.uniform(0.0, seconds, size=n))


def zipf_tags(m: int, n: int, count: int, rng: np.random.Generator, *,
              alpha: float = 1.2, unknown_frac: float = 0.0):
    """Zipf(``alpha``)-popular (team, device) tags over an ``m x n``
    population, ranks mapped onto devices through a fixed permutation
    (copied from ``repro.serve.personalized.zipf_requests``). An
    ``unknown_frac`` share is tagged with an out-of-range device, half of
    those with an out-of-range team too. Returns int32 arrays."""
    population = m * n
    ranks = (rng.zipf(alpha, size=count) - 1) % population
    flat = rng.permutation(population)[ranks]
    teams, devices = flat // n, flat % n
    if unknown_frac > 0.0:
        bad = rng.random(count) < unknown_frac
        devices = np.where(bad, n + 1, devices)
        teams = np.where(bad & (rng.random(count) < 0.5), m + 1, teams)
    return teams.astype(np.int32), devices.astype(np.int32)


def pad_size(count: int, sizes) -> int:
    """The smallest batch size in ``sizes`` that holds ``count``."""
    for s in sorted(sizes):
        if s >= count:
            return s
    raise ValueError(f"{count} requests exceed the largest batch {sizes}")


def lateness_ms(due_s, sent_s) -> np.ndarray:
    """How late each request was handed to the server, in ms."""
    return (np.asarray(sent_s) - np.asarray(due_s)) * 1e3


def latency_ms(due_s, done_s) -> np.ndarray:
    """Each request's latency from when it was due to when its answer
    was ready, in ms: a stall counts against every request behind it."""
    return (np.asarray(done_s) - np.asarray(due_s)) * 1e3
