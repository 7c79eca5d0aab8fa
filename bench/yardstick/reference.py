"""Plain reference of one sweep unit: the same semantics as the simulator,
written independently of it and importing nothing from it.

Given a configuration file's deployment, a traffic mix and a unit seed, it
draws the index trace, classifies every line access under each requested
on-chip configuration, times each batch's misses through the DRAM model and
assembles the per-batch and summary records that ``SimResult.to_json()``
prints. What differs from one workload kind to another (the line stream,
the vector work, the matrix ops) lives in the kind's module,
``bench/kinds/<workload.kind>.py``; the rest is one straight transcription
of the documented model:

* trace (``draw_batch``, ``table_stream``, ``table_batch_lines``, for
  kinds over equal tables):
  Zipf(s) inverse-CDF draw over the table's rows, then one row permutation
  per table (``numpy.random.default_rng(seed + batch)`` for both, as the
  simulator's generator does); table ``t`` row ``r`` starts at
  ``t * table_bytes + r * vector_bytes`` and touches
  ``ceil(vector_bytes / line_bytes)`` consecutive lines;
* on-chip: ``spm`` never hits; ``lru``/``srrip`` are set-associative caches
  with ChampSim replacement (set = line mod sets), all sets stepped in
  lockstep; ``pinning`` pins the most frequent lines up to capacity
  (ties by line address) and preloads them once;
* cores: lookups go to core ``sample mod cores``; each core has its own
  on-chip memory, and the cores' misses share one DRAM in trace order;
* DRAM: line -> block -> (channel, bank, row); per channel, banks are
  served round-robin one block at a time, per-bank order kept; a line's
  completion is ``max(bank free + activate if the row is closed, bus free)
  + bus cycles``, all in the precision the configuration states; each batch
  starts from an idle DRAM;
* batch cycles = max(on-chip streaming, DRAM finish, the kind's vector work), plus
  the analytic matrix model; energy = counts x per-action energies.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence

import numpy as np

from . import cells

MAX_RRPV = 3


# --------------------------------------------------------------------------
# Workload kind
# --------------------------------------------------------------------------

def kind(cfg: dict):
    """The module of ``bench/kinds`` that the configuration's
    ``workload.kind`` names. It gives the unit's line stream
    (``line_stream``: ``lines``, ``batch``, ``sample`` and ``num_batches``,
    as ``table_stream`` returns them), each batch's vector work
    (``vector_work``), the matrix ops (``matrix_ops``) and each batch's
    line accesses (``batch_lines``); everything else here is the same for
    every kind."""
    return cells.load_kind(cfg["workload"]["kind"])


# --------------------------------------------------------------------------
# Trace
# --------------------------------------------------------------------------

def draw_batch(spec: dict, zipf_s: float, seed: int):
    """``(table_ids, row_ids)`` of one batch of ``spec["batch"]`` samples,
    each looking up ``spec["lookups"]`` rows in each of ``spec["tables"]``
    tables of ``spec["rows"]`` rows, in execution order: a Zipf(s)
    inverse-CDF draw of ranks, then one row permutation per table."""
    n = spec["batch"] * spec["tables"] * spec["lookups"]
    rows = spec["rows"]
    rng = np.random.default_rng(seed)
    p = 1.0 / np.power(np.arange(1, rows + 1, dtype=np.float64), zipf_s)
    cdf = np.cumsum(p / p.sum())
    ranks = np.searchsorted(cdf, rng.random(n), side="right")
    index = rng.permutation(rows)[ranks]
    base = index.reshape(spec["batch"], spec["tables"], spec["lookups"])
    rng = np.random.default_rng(seed)
    row_ids = np.empty_like(base)
    for t in range(spec["tables"]):
        row_ids[:, t, :] = rng.permutation(rows)[base[:, t, :]]
    table_ids = np.broadcast_to(
        np.arange(spec["tables"])[None, :, None], base.shape)
    return table_ids.reshape(-1).astype(np.int64), row_ids.reshape(-1).astype(np.int64)


def table_stream(spec: dict, zipf_s: float, seed: int, line_bytes: int) -> dict:
    """Every line access of a unit over equal tables of ``spec["dim"]``-wide
    vectors, in order, with its batch and the sample (within its batch) that
    issued it; batch ``b`` is drawn from ``seed + b``."""
    vb = spec["dim"] * spec["dtype_bytes"]
    lpv = -(-vb // line_bytes)
    table_bytes = spec["rows"] * vb
    per_sample = spec["tables"] * spec["lookups"]
    lines, batch, sample = [], [], []
    for b in range(spec["num_batches"]):
        t, r = draw_batch(spec, zipf_s, seed + b)
        start = (t * table_bytes + r * vb) // line_bytes
        lines.append((start[:, None] + np.arange(lpv)[None, :]).reshape(-1))
        batch.append(np.full(start.size * lpv, b, dtype=np.int64))
        sample.append(np.repeat(np.arange(t.size) // per_sample, lpv))
    return dict(lines=np.concatenate(lines), batch=np.concatenate(batch),
                sample=np.concatenate(sample), lpv=lpv,
                num_batches=spec["num_batches"])


def table_batch_lines(spec: dict, line_bytes: int) -> List[int]:
    """Line accesses of each batch of ``table_stream``: the same for every
    batch and seed."""
    lpv = -(-spec["dim"] * spec["dtype_bytes"] // line_bytes)
    return [spec["batch"] * spec["tables"] * spec["lookups"] * lpv] * spec["num_batches"]


# --------------------------------------------------------------------------
# On-chip classification
# --------------------------------------------------------------------------

def cache_hits(lines: np.ndarray, num_sets: int, ways: int, policy: str) -> np.ndarray:
    """Hit flag of every access to a set-associative cache that starts empty.

    ChampSim replacement: LRU evicts the first invalid way, else the least
    recently used; SRRIP inserts at RRPV 2, promotes hits to 0 and evicts
    the first way at RRPV 3, ageing the set until one is. Sets never
    interact, so step ``t`` applies every set's ``t``-th access at once.
    """
    n = lines.size
    sets = lines % num_sets
    order = np.argsort(sets, kind="stable")
    count = np.bincount(sets, minlength=num_sets)
    first = np.cumsum(count) - count
    tags = np.full((num_sets, ways), -1, dtype=np.int64)
    meta = np.full((num_sets, ways), MAX_RRPV if policy == "srrip" else -1,
                   dtype=np.int64)
    hits = np.zeros(n, dtype=bool)
    for t in range(int(count.max()) if n else 0):
        act = np.nonzero(count > t)[0]
        pos = order[first[act] + t]
        x = lines[pos]
        tg, mt = tags[act], meta[act]
        match = tg == x[:, None]
        hit = match.any(axis=1)
        hits[pos] = hit
        rows = np.arange(act.size)
        if policy == "lru":
            invalid = tg < 0
            victim = np.where(invalid.any(axis=1), invalid.argmax(axis=1),
                              mt.argmin(axis=1))
            way = np.where(hit, match.argmax(axis=1), victim)
            tg[rows[~hit], way[~hit]] = x[~hit]
            mt[rows, way] = t
        elif policy == "srrip":
            miss = ~hit
            mt[rows[hit], match.argmax(axis=1)[hit]] = 0
            age = MAX_RRPV - mt.max(axis=1)
            mt[miss] += age[miss][:, None]
            victim = (mt == MAX_RRPV).argmax(axis=1)
            tg[rows[miss], victim[miss]] = x[miss]
            mt[rows[miss], victim[miss]] = MAX_RRPV - 1
        else:
            raise ValueError(f"no cache reference for policy {policy!r}")
        tags[act], meta[act] = tg, mt
    return hits


def classify(lines: np.ndarray, policy: str, capacity_bytes: int, ways: int,
             line_bytes: int):
    """``(hits, preload_writes)`` of one on-chip memory over its stream."""
    if policy == "spm":
        return np.zeros(lines.size, dtype=bool), 0
    capacity_lines = capacity_bytes // line_bytes
    if policy == "pinning":
        uniq, freq = np.unique(lines, return_counts=True)
        pinned = uniq[np.argsort(-freq, kind="stable")[:capacity_lines]]
        return np.isin(lines, pinned), int(pinned.size)
    return cache_hits(lines, max(1, capacity_lines // ways), ways, policy), 0


# --------------------------------------------------------------------------
# DRAM
# --------------------------------------------------------------------------

def dram_batch(lines: np.ndarray, off: dict, line_bytes: int, clock_ghz: float,
               ftype=np.float32):
    """``(finish cycle, row hits)`` of one batch's misses from an idle DRAM.

    ``ftype`` is the precision of the cycle arithmetic.
    """
    if lines.size == 0:
        return 0.0, 0
    C, B = off["channels"], off["banks_per_channel"]
    lpb = max(1, off["interleave_bytes"] // line_bytes)
    blocks_per_row = max(1, (off["row_bytes"] // line_bytes) // lpb)
    bus = ftype(line_bytes / (off["bandwidth_gbps"] / clock_ghz / C))
    act = ftype(off["t_rp_cycles"] + off["t_rcd_cycles"])
    zero = ftype(0.0)

    # Runs of consecutive lines in one block reach one bank back to back.
    blk = lines // lpb
    start = np.flatnonzero(np.r_[True, blk[1:] != blk[:-1]])
    length = np.diff(np.r_[start, lines.size])
    rblk = blk[start]
    queues = [[[] for _ in range(B)] for _ in range(C)]
    for b_, n_ in zip(rblk.tolist(), length.tolist()):
        in_ch = b_ // C
        q = queues[b_ % C][in_ch % B]
        if q and q[-1][0] == b_:
            q[-1][2] += n_
        else:
            q.append([b_, in_ch // B // blocks_per_row, n_])

    finish, row_hits = zero, 0
    for banks in queues:
        open_row = [-1] * B
        bank_free = [zero] * B
        bus_free = zero
        ptr = [0] * B
        left = sum(len(q) for q in banks)
        b = 0
        while left:
            while ptr[b] >= len(banks[b]):
                b = (b + 1) % B
            _, row, n_ = banks[b][ptr[b]]
            ptr[b] += 1
            left -= 1
            hit = open_row[b] == row
            done = max(bank_free[b] + (zero if hit else act), bus_free) + bus
            for _ in range(n_ - 1):          # the rest of the block: row hits
                done = done + bus
            row_hits += int(hit) + n_ - 1
            open_row[b], bank_free[b], bus_free = row, done, done
            b = (b + 1) % B
        finish = max(finish, bus_free)
    return float(finish + ftype(off["t_cas_cycles"])) + off["base_latency_cycles"], row_hits


# --------------------------------------------------------------------------
# Matrix model and energy
# --------------------------------------------------------------------------

def matrix_summary(ops: Sequence[tuple], hw: dict) -> dict:
    """Weight-stationary systolic timing, T = D/B + L transfers, double
    buffered: per batch cycles, on-chip reads/writes, DRAM lines, MACs."""
    R, C = hw["matrix_rows"], hw["matrix_cols"]
    line = hw["onchip"]["line_bytes"]
    off = hw["offchip"]
    bpc = off["bandwidth_gbps"] / hw["clock_ghz"]
    totals, reads, writes, dram_lines, flops = [], [], [], [], []
    for m, n, k, db, count in ops:
        comp = 0.0
        for ik in range(math.ceil(k / R)):
            k_t = min(R, k - ik * R)
            for jn in range(math.ceil(n / C)):
                c_t = min(C, n - jn * C)
                comp += k_t + m + k_t + c_t - 2
        comp *= count
        d_in, d_out = m * k * db + k * n * db, m * n * db
        mem = ((d_in + d_out) / bpc + off["base_latency_cycles"]) * count
        folds = max(1, math.ceil(k / R) * math.ceil(n / C))
        totals.append(mem / max(folds, 1) + max(comp, mem))
        reads.append(math.ceil(d_in / line) * count)
        writes.append(math.ceil((d_in + d_out) / line) * count)
        dram_lines.append(math.ceil((d_in + d_out) * count / line))
        flops.append(2 * m * n * k * count)
    return dict(cycles=sum(totals), reads=sum(reads), writes=sum(writes),
                dram_lines=sum(dram_lines), macs=sum(flops) / 2)


# --------------------------------------------------------------------------
# One unit
# --------------------------------------------------------------------------

def config_key(c: dict) -> tuple:
    return (c["policy"], int(c["capacity_bytes"]), int(c["ways"]),
            float(c["zipf_s"]), int(c["num_cores"]))


def simulate(cfg: dict, configs: Sequence[dict], seed: int,
             ftype=np.float32) -> Dict[tuple, dict]:
    """``{config_key: {"summary": ..., "batches": [...]}}`` for ``configs``
    (dicts with policy, capacity_bytes, ways, zipf_s, num_cores) of the
    unit drawn from ``seed``; ``ftype`` is the DRAM cycle precision."""
    hw, energy = cfg["hardware"], cfg["energy"]
    line, clock = hw["onchip"]["line_bytes"], hw["clock_ghz"]
    wk = kind(cfg)
    mat = matrix_summary(wk.matrix_ops(cfg), hw)
    streams: Dict[float, dict] = {}           # line stream of each zipf
    out = {}
    for c in configs:
        z = float(c["zipf_s"])
        if z not in streams:
            streams[z] = wk.line_stream(cfg, z, seed)
        st = streams[z]
        lines, batch, nb = st["lines"], st["batch"], st["num_batches"]
        cores = int(c["num_cores"])
        core = st["sample"] % cores
        hits = np.zeros(lines.size, dtype=bool)
        preload = 0
        for k in range(cores):
            sel = np.flatnonzero(core == k)
            h, p = classify(lines[sel], c["policy"], c["capacity_bytes"], c["ways"], line)
            hits[sel] = h
            preload += p
        batches = []
        for b in range(nb):
            in_b = batch == b
            reads = int(in_b.sum())
            hit_n = int((hits & in_b).sum())
            miss_n = reads - hit_n
            misses = lines[in_b & ~hits]
            finish, row_hits = dram_batch(misses, hw["offchip"], line, clock, ftype)
            onchip_cycles = max(
                int((in_b & (core == k)).sum()) * line / max(hw["onchip"]["read_bw_bytes_per_cycle"], 1)
                + hw["onchip"]["latency_cycles"] for k in range(cores))
            vector_ops, vec = wk.vector_work(cfg, st, in_b, core, cores)
            emb = max(onchip_cycles, finish, vec)
            batches.append(dict(
                batch_index=b,
                embedding_cycles=emb,
                matrix_cycles=mat["cycles"],
                total_cycles=emb + mat["cycles"],
                onchip_reads=mat["reads"] + reads,
                onchip_writes=mat["writes"] + miss_n + (preload if b == 0 else 0),
                offchip_reads=mat["dram_lines"] + miss_n,
                vector_ops=vector_ops,
                cache_hits=hit_n, cache_misses=miss_n,
                dram_row_hits=row_hits, dram_row_misses=int(misses.size) - row_hits,
                tlb_hits=0, tlb_misses=0, tlb_walks=0, translation_cycles=0.0,
            ))
        total_cycles = sum(b["total_cycles"] for b in batches)
        sums = {k: sum(b[k] for b in batches) for k in (
            "onchip_reads", "onchip_writes", "offchip_reads", "cache_hits", "cache_misses")}
        vec_ops = 0.0
        for b in batches:
            vec_ops += b["vector_ops"]
        energy_pj = (mat["macs"] * nb * energy["mac_bf16"]
                     + vec_ops * energy["vector_op"]
                     + (sums["onchip_reads"] * line * energy["onchip_read_per_byte"]
                        + sums["onchip_writes"] * line * energy["onchip_write_per_byte"])
                     + sums["offchip_reads"] * line * energy["offchip_per_byte"]
                     + total_cycles * energy["leakage_pj_per_cycle"]
                     + 0.0 * energy["tlb_walk_pj"])
        out[config_key(c)] = dict(
            summary=dict(
                total_cycles=total_cycles,
                embedding_cycles=sum(b["embedding_cycles"] for b in batches),
                matrix_cycles=sum(b["matrix_cycles"] for b in batches),
                energy_pj=energy_pj, num_batches=nb, **sums),
            batches=batches)
    return out
