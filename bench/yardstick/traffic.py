"""One generator for every traffic mix: always-on microphones, hop by hop.

A mix file (``bench/traffic/<name>.json``) gives the ingest mode
(``audio`` or ``feature``), ``chunk_hops``, the lane count, the loop
discipline and the audio source.  From ``--seed`` the generator makes a
bank of ``source.streams`` circular recordings, ``source.hops`` hops each:
background noise with keyword-like chirps at random places (the idea of
``keyword_event_stream`` in the program's data pipeline, kept here so the
program may change).  Lane ``i`` plays recording ``i % streams`` from a
phase drawn from the seed, so every lane hears its own audio while the
bank, and the set-up that makes it, stays the same size at any lane
count.

Feature ingest sends what an edge device would compute next to the
microphone: the reference MFCC frames of the same recordings.

``chunk(step)`` is a fresh host array for every step; ``lane_input`` gives
the reference the input of one lane's window.
"""

from __future__ import annotations

import numpy as np

from yardstick import reference


def rng_for(seed: int, tag: int) -> np.random.Generator:
    """A generator drawn from ``seed`` (any whole number) and a stream tag."""
    return np.random.default_rng([int(seed) % 2 ** 64, tag])


def pink_noise(rng: np.random.Generator, shape, rms: float) -> np.ndarray:
    """float32 noise whose power falls as 1/f, as room and street noise
    roughly does, scaled to ``rms``: white noise shaped in the frequency
    domain, so each row is seamless when it wraps around."""
    spec = np.fft.rfft(rng.standard_normal(shape), axis=-1)
    f = np.arange(spec.shape[-1], dtype=np.float64)
    spec[..., 0] = 0.0
    f[0] = 1.0
    x = np.fft.irfft(spec / np.sqrt(f), n=shape[-1], axis=-1)
    return (rms * x / np.sqrt(np.mean(x ** 2))).astype(np.float32)


def _chirp(n: int, sample_rate: int, f0: float, f1: float) -> np.ndarray:
    """A rising chirp from ``f0`` to ``f1`` Hz under a sin^2 envelope."""
    t = np.arange(n) / sample_rate
    dur = n / sample_rate
    phase = 2.0 * np.pi * (f0 * t + 0.5 * (f1 - f0) / dur * t * t)
    return (np.sin(np.pi * t / dur) ** 2) * np.sin(phase)


class Traffic:
    def __init__(self, mix: dict, seed: int, lanes: int, fr: dict):
        src = mix["source"]
        self.fr, self.lanes = fr, lanes
        self.feature = mix["ingest"] == "feature"
        if mix["ingest"] not in ("audio", "feature"):
            raise ValueError(f"unknown ingest {mix['ingest']!r}")
        if mix["loop"] != "closed":
            raise ValueError(f"unknown loop {mix['loop']!r}")
        self.k = int(mix["chunk_hops"])
        m, h, hop = int(src["streams"]), int(src["hops"]), fr["hop_len"]
        self.hops = h
        rng = rng_for(seed, 1)
        audio = pink_noise(rng, (m, h * hop), src["noise_rms"])
        # the noise level moves in steps, as a talker, a fan or traffic
        # comes and goes: one level in dB per ``level_hops`` hops
        seg = int(src["level_hops"])
        db = rng.uniform(*src["level_db"], (m, -(-h // seg)))
        gain = np.repeat(10.0 ** (db / 20.0), seg * hop, axis=1)
        audio *= gain[:, :h * hop].astype(np.float32)
        ev_len = int(src["event_hops"]) * hop
        template = _chirp(ev_len, fr["sample_rate"], *src["chirp_hz"])
        lo, hi = src["event_amp"]
        gap_lo, gap_hi = src["gap_hops"]
        for s in range(m):
            at = int(rng.integers(0, gap_hi)) * hop
            while at + ev_len <= h * hop:
                audio[s, at:at + ev_len] += (rng.uniform(lo, hi)
                                             * template).astype(np.float32)
                at += ev_len + int(rng.integers(gap_lo, gap_hi)) * hop
        self.audio = audio                                  # [M, H * hop]
        self.stream_of = np.arange(lanes) % m
        self.phase = rng_for(seed, 2).integers(0, h, lanes)
        if self.feature:
            self._rows = np.concatenate(                    # [M*H, F]
                [self._stream_frames(s) for s in range(m)])
        else:
            self._rows = audio.reshape(m * h, hop)          # [M*H, hop]

    def _stream_frames(self, s: int) -> np.ndarray:
        """[H, F] float32 frames of circular recording ``s``: frame j ends
        with hop j, its left context taken from the hops before."""
        ctx = self.fr["frame_len"] - self.fr["hop_len"]
        a = self.audio[s]
        return reference.mfcc(np.concatenate([a[-ctx:], a]),
                              self.fr).astype(np.float32)

    def rows(self, lanes: np.ndarray, step: int) -> np.ndarray:
        """Bank rows [len(lanes), k] that ``lanes`` play at ``step``."""
        hop_idx = (self.phase[lanes, None] + step * self.k
                   + np.arange(self.k)[None, :]) % self.hops
        return self.stream_of[lanes, None] * self.hops + hop_idx

    def chunk(self, step: int) -> np.ndarray:
        """The host array every lane sends at ``step``: audio
        [lanes, k * hop_len] or frames [lanes, k, F]."""
        rows = np.take(self._rows, self.rows(np.arange(self.lanes), step),
                       axis=0)
        if self.feature:
            return rows
        return rows.reshape(self.lanes, self.k * self.fr["hop_len"])

    def lane_input(self, lane: int, step: int, window: int) -> np.ndarray:
        """What the model window of ``lane`` holds after ``step``: the
        ``window`` newest frames [window, F] (feature ingest), or the audio
        they are made from, with its left context (audio ingest)."""
        last = (step + 1) * self.k                 # hops played so far
        ctx_hops = 0 if self.feature else self.context_hops
        hops = np.arange(last - window - ctx_hops, last)
        assert hops[0] >= 0, "the window reaches back before the join"
        idx = (self.stream_of[lane] * self.hops
               + (self.phase[lane] + hops) % self.hops)
        if self.feature:
            return self._rows[idx]
        ctx = self.fr["frame_len"] - self.fr["hop_len"]
        return self._rows[idx].reshape(-1)[ctx_hops * self.fr["hop_len"]
                                           - ctx:]

    @property
    def context_hops(self) -> int:
        """Whole hops that hold a frame's left context."""
        ctx = self.fr["frame_len"] - self.fr["hop_len"]
        return -(-ctx // self.fr["hop_len"])

    def warm_steps(self, window: int) -> int:
        """Steps after a join until every window frame, with its left
        context, comes from audio the lane has sent."""
        ctx_hops = 0 if self.feature else self.context_hops
        return -(-(window + ctx_hops) // self.k)
