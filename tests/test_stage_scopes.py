"""Each stage of a flat round runs under its ``fl.*`` named scope, and the
scope reaches the compiled engine's HLO metadata (``op_name``), which is
what a device trace is read by. A refactor that drops a scope fails here.
The hierarchical round shares the wireless stages (``fl/link.py``)."""
import re

import jax
import jax.numpy as jnp
import pytest

from benchmarks.common import make_linear_problem
from repro.core.faults import fault_params
from repro.core.hierarchy import HFLConfig
from repro.core.privacy.registry import privacy_params
from repro.data import make_linear_datagen
from repro.fl import runtime as rt

STAGES = ("fl.channel", "fl.schedule", "fl.data", "fl.local_update",
          "fl.compress", "fl.client_state", "fl.privacy", "fl.aggregate",
          "fl.server_update", "fl.log")

CASES = {
    # top-k with dense error feedback, on-device data, chunked client pass
    "topk-ef": (dict(compression="topk"),
                set(STAGES) - {"fl.privacy"}),
    # pairwise-masked secure aggregation over a field-compatible compressor
    # with error feedback, plus its mask pre-pass
    "secagg": (dict(compression="qsgd", privacy="secagg",
                    privacy_params=privacy_params(field_bits=24.0)),
               set(STAGES)),
}


def _compiled_scopes(**kw):
    params, loss_fn, _, w_star = make_linear_problem(d=16)
    dg = make_linear_datagen(w_star, local_steps=2, batch=4)
    cfg = rt.SimConfig(n_devices=16, n_scheduled=4, rounds=2, chunk_size=8,
                       datagen=dg, **kw)
    wcfg = rt.wireless.WirelessConfig(n_devices=cfg.n_devices)
    engine = rt._get_engine(cfg, wcfg, loss_fn, False)
    args = [jax.random.PRNGKey(0), rt.wireless.channel_params(wcfg),
            rt._resolve_cparams(cfg, params), rt._resolve_aparams(cfg)]
    if cfg.privacy != "none":
        args.append(rt._resolve_pparams(cfg))
    text = engine.lower(*args, jax.tree.map(jnp.array, params), None,
                        None).compile().as_text()
    return _scopes(text)


def _scopes(hlo_text):
    names = re.findall(r'op_name="([^"]*)"', hlo_text)
    return {s for n in names for s in re.findall(r"fl\.[a-z_]+", n)}


@pytest.mark.parametrize("case", sorted(CASES))
def test_every_stage_names_its_ops_in_the_compiled_engine(case):
    kw, expected = CASES[case]
    found = _compiled_scopes(**kw)
    assert expected <= found, sorted(expected - found)
    assert found <= set(STAGES), sorted(found - set(STAGES))


def test_hfl_round_names_its_channel_and_log_ops():
    """The hierarchical engine runs the shared channel layer under the
    flat round's scopes: its fault retries, clock and logs are staged."""
    params, loss_fn, make_batches, _ = make_linear_problem(d=16)
    cfg = rt.SimConfig(n_devices=12, n_scheduled=2, rounds=2,
                       compression="topk", max_retries=1,
                       faults=fault_params(drop_prob=0.2, snr_min=1.0))
    hcfg = HFLConfig(n_clusters=3, inter_cluster_period=2)
    wcfg = rt.wireless.WirelessConfig(n_devices=cfg.n_devices)
    engine = rt._get_engine(cfg, wcfg, loss_fn, False, hcfg=hcfg)
    batches = rt.stack_batches(make_batches, cfg.rounds, cfg.n_devices)
    text = engine.lower(
        jax.random.PRNGKey(0), rt.wireless.channel_params(wcfg),
        rt._resolve_cparams(cfg, params), rt._resolve_aparams(cfg),
        jnp.float32(hcfg.backhaul_rate_bps), cfg.faults, params, batches,
        None).compile().as_text()
    found = _scopes(text)
    assert {"fl.channel", "fl.log"} <= found, sorted(found)
    assert found <= set(STAGES), sorted(found - set(STAGES))
