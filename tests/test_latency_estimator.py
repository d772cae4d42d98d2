"""The latency estimator (Eqs. 10-17) against a plain copy of its earlier
form: a ``predict()`` that recomputes the lognormal long-period term on
every read, and a bisection that always runs its 80 steps.  The cached
term and the early stop must give the same bits."""
import numpy as np
import pytest

from repro.core import latency as LT


# --- the plain reference ------------------------------------------------------

def _plain_score_gamma(x, g):
    d = x - g
    ln = np.log(d)
    n = len(x)
    s1 = np.sum(1.0 / d)
    s2 = np.sum(ln)
    s3 = np.sum(ln * ln)
    s4 = np.sum(ln / d)
    return s1 * (s2 - s3 + s2 * s2 / n) - n * s4


def _plain_fit(x, iters=80):
    xa = np.asarray(list(x), dtype=np.float64)
    if len(xa) < 3 or np.any(xa <= 0):
        raise ValueError("need >=3 positive samples")
    xmin = float(np.min(xa))
    lo, hi = 1e-12, xmin * (1.0 - 1e-9)
    flo, fhi = _plain_score_gamma(xa, lo), _plain_score_gamma(xa, hi)
    if flo * fhi > 0:
        gamma = 0.0
    else:
        for _ in range(iters):
            mid = 0.5 * (lo + hi)
            fm = _plain_score_gamma(xa, mid)
            if flo * fm <= 0:
                hi, fhi = mid, fm
            else:
                lo, flo = mid, fm
        gamma = 0.5 * (lo + hi)
    ln = np.log(xa - gamma)
    mu = float(np.mean(ln))
    sigma2 = float(np.mean((ln - mu) ** 2))
    return gamma, mu, sigma2


class _PlainEstimator:
    refits = bisect_steps = 0      # the earlier form counted nothing

    def __init__(self, t=0.1, history_max=256, refit_every=64, blend=0.5):
        self.t, self.history_max = t, history_max
        self.refit_every, self.blend = refit_every, blend
        self._history, self._since_fit, self._lognormal = [], 0, None

    def observe(self, t_new):
        self.t = LT.adaptive_mean(self.t, t_new)
        self._history.append(float(t_new))
        if len(self._history) > self.history_max:
            self._history = self._history[-self.history_max:]
        self._since_fit += 1
        if self._since_fit >= self.refit_every and len(self._history) >= 8:
            try:
                self._lognormal = _plain_fit(self._history)
            except (ValueError, FloatingPointError):
                self._lognormal = None
            self._since_fit = 0
        return self.t

    def predict(self):
        if self._lognormal is None:
            return self.t
        g, mu, s2 = self._lognormal
        mean = g + np.exp(mu + s2 / 2.0)
        median = g + np.exp(mu)
        longterm = 0.5 * (mean + median)
        return (1 - self.blend) * self.t + self.blend * float(longterm)


# --- seeded histories ---------------------------------------------------------

KINDS = ("lognormal", "bimodal", "constant")
N_HISTORIES = 200


def _history(kind, seed, n):
    rng = np.random.default_rng(seed)
    if kind == "lognormal":
        return rng.uniform(0.0, 0.05) + np.exp(
            rng.normal(rng.uniform(-4.0, 0.0), rng.uniform(0.05, 1.2), n))
    if kind == "bimodal":
        fast = 0.01 + np.exp(rng.normal(-4.0, 0.2, n))
        slow = 0.2 + np.exp(rng.normal(-1.5, 0.3, n))
        return np.where(rng.random(n) < rng.uniform(0.1, 0.9), fast, slow)
    return np.full(n, float(rng.choice((0.05, 0.1, 0.3, 1.0, 2.5))))


def _histories(kind):
    return [_history(kind, 1000 * KINDS.index(kind) + i,
                     int(np.random.default_rng(i).integers(8, 300)))
            for i in range(N_HISTORIES)]


def _bits(x):
    return np.float64(x).tobytes()


@pytest.mark.parametrize("kind", KINDS)
def test_fit_lognormal3_bit_identical(kind):
    fallbacks = 0
    for x in _histories(kind):
        want = _plain_fit(x)
        got = LT.fit_lognormal3(x)
        assert [_bits(v) for v in got] == [_bits(v) for v in want]
        # a list input, as the estimator's history was, reads the same
        assert LT.fit_lognormal3(list(x)) == got
        fallbacks += want[0] == 0.0
    if kind == "constant":
        # the no-bracket gamma = 0 fallback is among the cases compared
        assert fallbacks > 0


@pytest.mark.parametrize("kind", KINDS)
def test_predict_bit_identical_across_refits_and_writes(kind):
    refits = 0
    for i, x in enumerate(_histories(kind)):
        rng = np.random.default_rng(7000 + i)
        kw = dict(t=float(rng.uniform(0.01, 1.0)),
                  refit_every=int(rng.choice((16, 32, 64))),
                  history_max=int(rng.choice((64, 256))))
        new, old = LT.LatencyEstimator(**kw), _PlainEstimator(**kw)
        for k, v in enumerate(x):
            assert _bits(new.observe(v)) == _bits(old.observe(v))
            assert _bits(new.predict()) == _bits(old.predict())
            if k % 37 == 36:
                # callers seed and overwrite t directly; predict follows
                new.t = old.t = float(rng.uniform(0.01, 1.0))
                assert _bits(new.predict()) == _bits(old.predict())
        assert list(new._history) == old._history
        refits += new.refits
    assert refits > N_HISTORIES


def test_bisection_stops_early_and_counts_its_steps():
    est = LT.LatencyEstimator(refit_every=64)
    for v in _history("lognormal", 5, 256):
        est.observe(v)
    assert est.refits == 4
    assert 0 < est.bisect_steps < 80 * est.refits
