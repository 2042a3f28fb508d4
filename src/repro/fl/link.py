"""The wireless round of the flat and the hierarchical engine (paper §III).

Everything on the air in one round of ``run_simulation_scan`` and
``run_hfl``: the link state in the scan carry, the uplink draw, payload
pricing, dropout / decode failure / retransmissions, uplink billing, the
downlink and the slowest-device clock, fault logs, Rényi DP accounting and
the round's outputs. What differs between the engines enters as arguments
of :class:`Link` (per-device ``ChannelParams`` and distances, the rate
function, the peers a client agrees masks with, the downlink bits, the DP
noise multiplier); each piece runs under the ``fl.*`` scope that names its
operations in a device trace.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import faults as faults_lib
from repro.core import wireless
from repro.core.compression import registry as compression
from repro.core.compression.registry import CompressionParams
from repro.core.privacy import registry as privacy_lib


def message_bits_jax(compression_name: str, cparams: CompressionParams,
                     model_bits: float, d_model: int) -> jnp.ndarray:
    """Simulated bits-on-the-wire of one model-sized message: ``model_bits``
    scaled by the compressor's bits-per-parameter rate on the actual d-dim
    message (data-independent, so a round can be priced *before*
    transmission). ``"none"`` sends exactly ``model_bits``. Shared pricing
    model of the flat, HFL, and gossip/fog engines
    (``fl/decentralized.py``)."""
    if compression_name == "none":
        return jnp.float32(model_bits)
    payload_scale = model_bits / (32.0 * d_model)
    return payload_scale * compression.uplink_bits_jax(
        compression_name, cparams, d_model)


def charge(bits, w: jnp.ndarray) -> jnp.ndarray:
    """``bits`` per attempt (one figure, or one per device) times the
    per-device attempt counts ``w``, summed over devices."""
    return bits * jnp.sum(w) if jnp.ndim(bits) == 0 else jnp.sum(bits * w)


class LinkState(NamedTuple):
    """The link part of the scan carry. Its leaves flatten in this field
    order; a field is None where its static switch (faults, DP) is off."""
    clock: jnp.ndarray                    # simulated wall clock (s)
    ages: jnp.ndarray                     # (N,) rounds since scheduled
    norms: jnp.ndarray                    # (N,) update-norm proxy
    avg_snr: jnp.ndarray                  # (N,) time-averaged SNR (PF)
    avail: Optional[jnp.ndarray] = None   # (N,) bool churn availability
    fad: Optional[jnp.ndarray] = None     # (N, 2) Gauss-Markov fading
    stal: Optional[jnp.ndarray] = None    # (N,) per-client staleness
    rdp: Optional[jnp.ndarray] = None     # Rényi ledger, one per ALPHAS

    @classmethod
    def init(cls, n: int, faults_on: bool, dp_on: bool) -> "LinkState":
        # everyone starts online, with a zero fading state and staleness
        return cls(jnp.float32(0.0), jnp.zeros(n, jnp.float32),
                   jnp.ones(n, jnp.float32), jnp.zeros(n, jnp.float32),
                   *((jnp.ones(n, dtype=bool), jnp.zeros((n, 2), jnp.float32),
                      jnp.zeros(n, jnp.float32)) if faults_on
                     else (None,) * 3),
                   jnp.zeros(len(privacy_lib.ALPHAS), jnp.float32)
                   if dp_on else None)


class Uplink(NamedTuple):
    snr_lin: jnp.ndarray      # (N,) uplink SNR
    rates: jnp.ndarray        # (N,) uplink rates
    comp_lat: jnp.ndarray     # (N,) compute latency, stragglers included
    bits_dev: jnp.ndarray     # bits of one upload attempt (scalar or (N,))
    mask_over: jnp.ndarray    # secagg key-agreement bits per client
    comm_lat: jnp.ndarray     # (N,) airtime of one upload attempt
    payload_scale: float      # simulated bits per float32 message bit


class Fate(NamedTuple):
    """What faults did to this round's uploads."""
    dropped: jnp.ndarray      # (N,) scheduled and vanished mid-round
    ok: jnp.ndarray           # (N,) decoded by the last attempt
    comm_eff: jnp.ndarray     # (N,) airtime of every attempt
    n_retx: jnp.ndarray       # (N,) retransmissions
    survived: jnp.ndarray     # (N,) scheduled, not dropped, decoded


class RoundOut(NamedTuple):
    """One round's outputs; the scan stacks them, and ``SimLogs`` takes
    its fields by name."""
    loss: jnp.ndarray
    latency_s: jnp.ndarray
    participation: jnp.ndarray
    n_scheduled: jnp.ndarray
    uplink_bits: jnp.ndarray
    comm_s: jnp.ndarray
    comp_s: jnp.ndarray
    downlink_bits: jnp.ndarray
    n_survived: jnp.ndarray
    n_dropped: jnp.ndarray
    retransmissions: jnp.ndarray
    staleness_mean: jnp.ndarray
    epsilon: jnp.ndarray
    delta: jnp.ndarray
    mask_bits: jnp.ndarray


class Link(NamedTuple):
    """The channel layer of one engine run: what stays fixed over its
    rounds. ``cfg`` is the run's ``SimConfig``."""
    cfg: Any
    uplink_factor: float          # message-sized uploads a round (algo)
    priv: Any                     # privacy mechanism, None for "none"
    chan: wireless.ChannelParams  # shared, or gathered per device
    dist: jnp.ndarray             # (N,) device -> receiver distances
    rate_fn: Callable             # SNR -> uplink rate
    mask_peers: Any               # peers a client agrees masks with
    fparams: Any                  # FaultParams, None without faults
    pparams: Any                  # PrivacyParams, None without privacy

    def uplink(self, ls: LinkState, kt: jax.Array, kf: jax.Array,
               kc: jax.Array, t, d_model: int, cparams: CompressionParams):
        """Draw every device's channel (``kf``: i.i.d. fading; ``kt``: the
        round key of the fault streams) and compute time (``kc``) and price
        its payload. Returns ``(link state, Uplink)``."""
        cfg, fp, priv = self.cfg, self.fparams, self.priv
        n = cfg.n_devices
        with jax.named_scope("fl.channel"):
            fad = ls.fad
            if fp is not None:
                # temporally correlated fading replaces the i.i.d. draw;
                # round 0 draws the stationary distribution so rho=0
                # recovers the i.i.d. Rayleigh marginal
                fad, fading = faults_lib.gauss_markov_fading(fp, kt, fad, t)
            else:
                fading = wireless.sample_fading_jax(kf, n)
            snr_lin = wireless.snr_jax(self.dist, fading, self.chan)
            rates = self.rate_fn(snr_lin)
            comp_lat = cfg.comp_latency_s * jax.random.exponential(kc, (n,))
            if fp is not None:
                # heavy-tailed straggler tail on top of the exponential base
                comp_lat = comp_lat * faults_lib.straggler_multiplier(
                    fp, kt, n)
            # the payload is model_bits scaled by the compressor's
            # bits-per-parameter rate on the d-dim message (data-independent,
            # so the policies can price the round *before* transmission),
            # times the algorithm's uploads a round (SCAFFOLD: delta + ctrl)
            payload_scale = cfg.model_bits / (32.0 * d_model)
            if cfg.compression != "none":
                bits_dev = message_bits_jax(
                    cfg.compression, cparams, cfg.model_bits,
                    d_model) * self.uplink_factor
            else:
                bits_dev = jnp.float32(cfg.model_bits * self.uplink_factor)
            mask_over = jnp.float32(0.0)
            if priv is not None:
                # field modes replace the compressor's rate with dense
                # field_bits per coordinate (a masked message is
                # incompressible); the pairwise key agreement adds raw
                # protocol bits per round — both priced on the uplink
                if priv.uses_field:
                    bits_dev = payload_scale * privacy_lib.uplink_bits_jax(
                        cfg.privacy, self.pparams, d_model,
                        0.0) * self.uplink_factor
                if priv.uses_masks:
                    mask_over = privacy_lib.mask_bits_jax(cfg.privacy,
                                                          self.mask_peers)
                    bits_dev = bits_dev + mask_over
            comm_lat = wireless.comm_latency_jax(bits_dev, rates)
            # per-device time-averaged SNR (PF's denominator), seeded with
            # the first observation
            avg_snr = jnp.where(t == 0, snr_lin,
                                0.9 * ls.avg_snr + 0.1 * snr_lin)
        return ls._replace(fad=fad, avg_snr=avg_snr), Uplink(
            snr_lin, rates, comp_lat, bits_dev, mask_over, comm_lat,
            payload_scale)

    def retransmit(self, kt: jax.Array, up: Uplink, mask: jnp.ndarray):
        """Mid-round dropout and SNR-threshold decode failure with up to
        ``max_retries`` retransmissions, each re-sampling the channel and
        re-billing the payload's airtime. Returns ``(Fate, participation)``,
        the Fate None without faults."""
        fp, n = self.fparams, self.cfg.n_devices
        with jax.named_scope("fl.channel"):
            if fp is None:
                return None, mask.astype(jnp.float32)
            dropped = faults_lib.dropout_draw(fp, kt, n) & mask
            ok = up.snr_lin >= fp.snr_min
            comm_eff = up.comm_lat
            n_retx = jnp.zeros(n, jnp.float32)
            for r in range(1, self.cfg.max_retries + 1):
                fad_r = faults_lib.retry_fading(kt, r, n)
                snr_r = wireless.snr_jax(self.dist, fad_r, self.chan)
                lat_r = wireless.comm_latency_jax(up.bits_dev,
                                                  self.rate_fn(snr_r))
                need = ~ok
                comm_eff = comm_eff + jnp.where(need, lat_r, 0.0)
                n_retx = n_retx + need.astype(jnp.float32)
                ok = ok | (snr_r >= fp.snr_min)
            survived = mask & ~dropped & ok
            return (Fate(dropped, ok, comm_eff, n_retx, survived),
                    survived.astype(jnp.float32))

    def bill(self, up: Uplink, mask: jnp.ndarray, fate: Optional[Fate],
             sent_bits=None) -> jnp.ndarray:
        """Uplink bits on the air: ``bits_dev`` per attempt of a scheduled,
        undropped device, or the decoded payloads' message bits as the
        client pass counted them (``sent_bits``) plus every *scheduled*
        client's key agreement and the undecoded attempts."""
        with jax.named_scope("fl.compress"):
            if sent_bits is None:
                if fate is None:
                    return charge(up.bits_dev, mask)
                return charge(up.bits_dev, jnp.where(
                    mask & ~fate.dropped, 1.0 + fate.n_retx, 0.0))
            ubits = up.payload_scale * sent_bits
            if self.priv is not None and self.priv.uses_masks:
                ubits = ubits + charge(up.mask_over, mask)
            if fate is not None:
                # retries plus the final failed payload of never-decoded
                # clients
                ubits = ubits + charge(up.bits_dev, jnp.where(
                    mask & ~fate.dropped,
                    fate.n_retx + (~fate.ok).astype(jnp.float32), 0.0))
            return ubits

    def close(self, ls: LinkState, kt: jax.Array, up: Uplink,
              mask: jnp.ndarray, part: jnp.ndarray, fate: Optional[Fate],
              ubits, dl_bits, z_eff: Callable, extra_s=None):
        """Price the broadcast of ``dl_bits`` that opens the round, advance
        the clock by the slowest scheduled device's downlink, upload and
        compute (plus ``extra_s``), log faults and account one subsampled
        Gaussian round at fraction survivors / N and noise multiplier
        ``z_eff(survivors)``. Returns ``(link state, RoundOut)`` with the
        ``loss`` and ``n_scheduled`` that :meth:`finish` fills."""
        n = self.cfg.n_devices
        with jax.named_scope("fl.channel"):
            dl_rate = wireless.shannon_rate_jax(
                wireless.downlink_snr_jax(
                    self.dist, faults_lib.downlink_fading(kt, n), self.chan),
                self.chan.bandwidth_hz)
            dl_lat = wireless.comm_latency_jax(dl_bits, dl_rate)
            any_sched = jnp.any(mask)
            dl_s = jnp.max(jnp.where(mask, dl_lat, 0.0))
            dl_bits_out = jnp.where(any_sched, dl_bits, jnp.float32(0.0))
            # the comm/comp breakdown is the bottleneck device's split. A
            # dropped client stops consuming the round (the server's
            # deadline machinery already excluded it), a decode-failed one
            # still burns its airtime.
            if fate is not None:
                comm_c = jnp.where(fate.dropped, 0.0, fate.comm_eff)
                comp_c = jnp.where(fate.dropped, 0.0, up.comp_lat)
            else:
                comm_c, comp_c = up.comm_lat, up.comp_lat
            total = comm_c + comp_c
            slowest = jnp.argmax(jnp.where(mask, total, -jnp.inf))
            comm_s = jnp.where(any_sched, comm_c[slowest], 0.0)
            comp_s = jnp.where(any_sched, comp_c[slowest], 0.0)
            clock = ls.clock + dl_s + comm_s + comp_s
            if extra_s is not None:
                clock = clock + extra_s
        with jax.named_scope("fl.log"):
            stal = ls.stal
            if fate is not None:
                # staleness before this round's resets: a client
                # aggregated now was stale by the rounds it sat out
                stal_log = jnp.mean(stal)
                stal = jnp.where(fate.survived, 0.0, stal + 1.0)
                retx_log = jnp.sum(jnp.where(mask & ~fate.dropped,
                                             fate.n_retx, 0.0))
                n_surv = jnp.sum(fate.survived).astype(jnp.int32)
                n_drop = jnp.sum(mask & ~fate.survived).astype(jnp.int32)
            else:
                stal_log = jnp.float32(0.0)
                retx_log = jnp.float32(0.0)
                n_surv = jnp.sum(mask).astype(jnp.int32)
                n_drop = jnp.int32(0)
            rdp = ls.rdp
            if rdp is not None:
                n_surv_f = jnp.sum(part)
                q_frac = n_surv_f / n
                rdp = rdp + privacy_lib.rdp_increment(q_frac, z_eff(n_surv_f))
                eps = privacy_lib.epsilon_of(rdp)
                delta_out = jnp.float32(privacy_lib.DELTA)
            else:
                eps = jnp.float32(jnp.inf)
                delta_out = jnp.float32(1.0)
            mask_bits_out = charge(up.mask_over, mask)
        return ls._replace(clock=clock, stal=stal, rdp=rdp), RoundOut(
            None, clock, mask, None, ubits, comm_s, comp_s, dl_bits_out,
            n_surv, n_drop, retx_log, stal_log, eps, delta_out,
            mask_bits_out)

    def finish(self, ls: LinkState, kn: jax.Array, out: RoundOut,
               loss) -> tuple:
        """Advance the update-norm proxy the update-aware policies observe
        (drawn from ``kn``) and complete the round's outputs."""
        with jax.named_scope("fl.schedule"):
            norms = 0.9 * ls.norms + 0.1 * jax.random.exponential(
                kn, (self.cfg.n_devices,))
        return ls._replace(norms=norms), out._replace(
            loss=loss, n_scheduled=jnp.sum(out.participation))
