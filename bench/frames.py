"""Camera frames for the benchmark, made in bulk from a seed.

The benchmark's own copy of the two synthetic scenes the system serves
(``data/tollbooth.py``, ``data/volleyball.py``): the same layout, colours,
car sprites, players and sensor noise, rendered without labels and fast
enough that a fleet's whole window of frames is made during set-up.

Departures from the originals: scene dynamics and sensor noise draw
from separate generators; the noise of each frame is one of a pool of
256 noise frames, picked per frame from the seed (two consecutive frames
still carry independent noise, so the frame differences the Skip
operator sees keep their statistics); and a toll feed's cars come from a
fixed multiset per feed, shuffled by the seed (``car_schedule``), with a
geometric gap of mean ``1 / car_rate`` frames between spawn chances.
"""
from __future__ import annotations

from typing import List

import numpy as np

H, W = 128, 256
NOISE_POOL = 256

COLORS = ["red", "blue", "green", "white", "black", "yellow"]
COLOR_RGB = {
    "red": (200, 30, 30), "blue": (30, 60, 200), "green": (30, 170, 60),
    "white": (230, 230, 230), "black": (25, 25, 25),
    "yellow": (220, 210, 40),
}
N_BRANDS = 6
PLATE_CHARS = "ABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789"
FONT = {
    "A": "010101111101101", "B": "110101110101110", "C": "011100100100011",
    "D": "110101101101110", "E": "111100110100111", "F": "111100110100100",
    "G": "011100101101011", "H": "101101111101101", "I": "111010010010111",
    "J": "001001001101010", "K": "101110100110101", "L": "100100100100111",
    "M": "101111111101101", "N": "101111111111101", "O": "010101101101010",
    "P": "110101110100100", "Q": "010101101011001", "R": "110101110110101",
    "S": "011100010001110", "T": "111010010010010", "U": "101101101101111",
    "V": "101101101101010", "W": "101101111111101", "X": "101010010010101",
    "Y": "101101010010010", "Z": "111001010100111",
    "0": "010101101101010", "1": "010110010010111", "2": "110001010100111",
    "3": "110001010001110", "4": "101101111001001", "5": "111100110001110",
    "6": "011100110101010", "7": "111001010010010", "8": "010101010101010",
    "9": "010101011001110",
}
CAR_H, CAR_W, CAR_Y = 44, 88, 72
PLATE_H, PLATE_W, GLYPH = 19, 84, 3
READ_ZONE = (78.0, 98.0)
ZONE_SLOWDOWN = 0.35


def noise_pool(rng: np.random.Generator, high: int) -> np.ndarray:
    return rng.integers(0, high, size=(NOISE_POOL, 3, H, W), dtype=np.uint8)


def _car_sprite(color: str, brand: int, plate: str) -> np.ndarray:
    """One car as drawn by ``TollBoothStream._render_car`` at x = 0."""
    s = np.empty((3, CAR_H, CAR_W), np.uint8)
    s[:] = np.asarray(COLOR_RGB[color], np.uint8)[:, None, None]
    n_stripes = brand + 1
    gap = (CAR_W - 16) // n_stripes
    for k in range(n_stripes):
        x0 = 8 + k * gap
        s[:, 4:12, x0:x0 + 4] = 10
    py0 = CAR_H - PLATE_H - 2
    s[:, py0:py0 + PLATE_H, 2:2 + PLATE_W] = 245
    for ci, ch in enumerate(plate):
        bits = FONT[ch]
        gx0, gy0 = 4 + ci * (3 * GLYPH + 5), py0 + 2
        for r in range(5):
            for c in range(3):
                if bits[r * 3 + c] == "1":
                    s[:, gy0 + r * GLYPH:gy0 + (r + 1) * GLYPH,
                      gx0 + c * GLYPH:gx0 + (c + 1) * GLYPH] = 5
    return s


def car_schedule(n: int, car_rate: float, schedule: List[int]):
    """Gaps between spawn chances and speeds of the cars of one feed.

    They come from ``schedule`` (the feed's place in the fleet and the
    mix's car rate), not from the run's seed: every seed offers the same
    cars at the same times and speeds, so the seed changes what the cars
    and the sensor noise look like, never how many frames show a car."""
    fixed = np.random.default_rng(schedule)
    gaps = fixed.geometric(car_rate, size=max(4, int(3 * n * car_rate) + 4))
    gaps = gaps[:int(np.searchsorted(np.cumsum(gaps), n)) + 1]
    return gaps, 4.0 + 3.0 * fixed.random(len(gaps))


def tollbooth(n: int, seed: List[int], pool: np.ndarray, car_rate: float,
              schedule: List[int], stolen_rate: float = 0.15,
              repeat_rate: float = 0.25) -> np.ndarray:
    """``n`` toll-lane frames (uint8, (n, 3, 128, 256)); ``seed`` is the
    entropy of this feed's draws, ``schedule`` that of its car multiset
    (``car_schedule``)."""
    rs = np.random.default_rng(seed + [0])
    gaps, speeds = car_schedule(n, car_rate, schedule)
    next_car, due = 0, int(gaps[0]) if len(gaps) else n
    bg = np.zeros((3, H, W), np.uint8)
    bg[:, :H // 2] = 150
    bg[0, :H // 2] = 140
    bg[2, :H // 2] = 170
    bg[:, H // 2:] = 90
    bg[:, H - 8:H - 6] = 180
    frames = pool[np.random.default_rng(seed + [1]).integers(
        0, len(pool), n)]
    frames += bg
    cars: List[list] = []                 # [x, speed, sprite]
    past: List[tuple] = []
    for i in range(n):
        if i >= due and next_car < len(gaps) and \
                (not cars or cars[-1][0] > 60):
            if past and rs.random() < repeat_rate:
                color, brand, plate = past[rs.integers(len(past))]
            else:
                color = COLORS[rs.integers(len(COLORS))]
                brand = int(rs.integers(N_BRANDS))
                if rs.random() < stolen_rate:
                    prefix, color = "MTT", "red"
                else:
                    prefix = "".join(PLATE_CHARS[j]
                                     for j in rs.integers(0, 26, 3))
                    if prefix == "MTT":
                        prefix = "AAA"
                plate = prefix + "".join(str(d)
                                         for d in rs.integers(0, 10, 3))
                past.append((color, brand, plate))
            cars.append([-CAR_W - 1.0, float(speeds[next_car]),
                         _car_sprite(color, brand, plate)])
            next_car += 1
            due = i + int(gaps[next_car]) if next_car < len(gaps) else n
        for car in cars:
            in_zone = READ_ZONE[0] - 10 <= car[0] <= READ_ZONE[1] + 4
            car[0] += car[1] * (ZONE_SLOWDOWN if in_zone else 1.0)
        cars = [c for c in cars if c[0] < W + 2]
        for x, _, sprite in cars:
            x0 = int(round(x))
            a, b = max(0, x0), min(W, x0 + CAR_W)
            if b > a:
                frames[i, :, CAR_Y:CAR_Y + CAR_H, a:b] = \
                    sprite[:, :, a - x0:b - x0]
    return frames


TEAM_RGB = {0: (220, 60, 60), 1: (60, 90, 220)}


def _court(cam: int) -> np.ndarray:
    f = np.zeros((3, H, W), np.uint8)
    xs = np.arange(W) + cam
    f[:, :H // 3, :] = (40 + 30 * ((xs // 16) % 2)).astype(np.uint8)
    f[:, H // 3:, :] = 120
    net_x = W // 2 + (cam % 5) - 2
    f[:, 40:100, net_x:net_x + 2] = 220
    return f


def volleyball(n: int, seed: List[int], pool: np.ndarray) -> np.ndarray:
    """``n`` court frames (uint8, (n, 3, 128, 256)), moving camera."""
    rs = np.random.default_rng(seed + [0])
    courts = np.stack([_court(c) for c in range(32)])
    players = []
    for team in (0, 1):
        for i in range(6):
            players.append((team, 24 + i * 32 + (8 if team else -8),
                            70 + 22 * team + int(rs.integers(-4, 5))))
    bx, by, vx, vy = W / 2, 40.0, 2.0, 0.0
    phase, phase_t, cam_f = "idle", 0, 0.0
    cams = np.empty(n, np.int64)
    draws = []
    for i in range(n):
        phase_t += 1
        if phase == "idle" and rs.random() < 0.08:
            phase, phase_t, vy = "pass", 0, -3.0
            vx = 2.0 * (1 if rs.random() < 0.5 else -1)
        elif phase == "pass" and phase_t > 8:
            phase, phase_t, vy = "set", 0, -4.0
        elif phase == "set" and phase_t > 10:
            phase, phase_t, vy = "spike", 0, 6.0
            vx = 3.0 * (1 if vx > 0 else -1)
        elif phase == "spike" and phase_t > 6:
            phase, phase_t, vy, vx = "idle", 0, 0.0, 1.0
        if phase in ("pass", "set"):
            vy += 0.3
        bx += vx
        by = float(np.clip(by + vy, 16, 100))
        if bx < 10 or bx > W - 10:
            vx = -vx
        attack = 0 if vx > 0 else 1
        cam_f += rs.standard_normal() * 1.5 + 0.2
        cams[i] = int(round(cam_f)) % 32
        jump = [phase in ("set", "spike") and abs(px - bx) < 24
                and team == attack for team, px, _ in players]
        draws.append((jump, int(bx), int(by)))
    frames = courts[cams]
    frames += pool[np.random.default_rng(seed + [1]).integers(
        0, len(pool), n)]
    for i, (jump, ibx, iby) in enumerate(draws):
        cam = int(cams[i])
        f = frames[i]
        for (team, px, py), j in zip(players, jump):
            x, y = px + cam // 2, py - (8 if j else 0)
            f[:, max(0, y - 8):min(H, y + 8), max(0, x - 4):min(W, x + 4)] = \
                np.asarray(TEAM_RGB[team], np.uint8)[:, None, None]
        f[:, max(0, iby - 3):iby + 3, max(0, ibx - 3):ibx + 3] = 250
    return frames
