"""Every Pallas rung compiled by Mosaic and held to its registered XLA
reference at published widths: ``kernel_lib/parity.py``'s runners with
``native=True`` over its chip matrix — the same builders the CPU suite
drives in interpret mode, so the two runs share one matrix."""

import functools
import itertools

import pytest

from automodel_tpu.ops.kernel_lib import parity, registry

_RUNNERS = {
    "attention.splash": parity.run_attention_parity,
    "attention.paged_decode": parity.run_paged_attention_parity,
    "attention.mla_paged_decode": parity.run_mla_paged_attention_parity,
    "attention.retention_decode": parity.run_retention_parity,
    "attention.retention_chunk": parity.run_retention_parity,
    "linear_ce.pallas": parity.run_linear_ce_parity,
    # grads=True: dlhs is a second gmm, drhs the transposed kernel (tgmm)
    "gmm.pallas": functools.partial(parity.run_gmm_parity, grads=True),
    "moe_decode.pallas": parity.run_moe_decode_parity,
    "qdot.pallas": parity.run_qdot_parity,
    "gmm_quant.pallas": parity.run_gmm_quant_parity,
}
_CASES = [(rung, case) for rung, cases in parity.chip_cases().items()
          for case in cases]


@pytest.mark.parametrize(
    "rung,case", _CASES, ids=[f"{r}-{c['name']}" for r, c in _CASES])
def test_pallas_rung_matches_reference_natively(rung, case, record_property):
    err = _RUNNERS[rung](rung, case, native=True)
    if rung == "gmm.pallas":
        for name, e in zip(("max_err", "max_err_dlhs", "max_err_tgmm"), err):
            record_property(name, e)
    else:
        record_property("max_err", err)


def test_probes_accept_published_widths_on_the_chip():
    """Dispatch (not just the harness) picks the Pallas rung here."""
    brumby = {"num_q_heads": 40, "num_kv_heads": 8, "head_dim": 128,
              "value_dim": 128, "state_dtype": "float32"}
    for head, request in (
            ("attention.splash", {"q_seq": 2048, "kv_seq": 2048,
                                  "head_dim": 64}),
            ("attention.paged_decode", {"q_seq": 1, "head_dim": 128}),
            ("attention.paged_decode", {"q_seq": 5, "head_dim": 128}),
            ("attention.mla_paged_decode",
             {"q_seq": 1, "latent_dim": 640, "value_dim": 512}),
            ("attention.mla_paged_decode",
             {"q_seq": 64, "latent_dim": 640, "value_dim": 512}),
            ("attention.retention_decode", dict(brumby, q_seq=1)),
            ("attention.retention_chunk", dict(brumby, q_seq=64)),
            ("linear_ce.pallas", {"t": 16384, "h": 2048, "v": 128256}),
            ("gmm.pallas", {"m": 4096, "k": 4096, "n": 14336}),
            ("moe_decode.pallas", {"rows": 64, "hidden": 7168, "inter": 2048,
                                   "experts": 12}),
            ("moe_decode.pallas", {"rows": 48, "hidden": 2560, "inter": 768,
                                   "experts": 64}),
            ("qdot.pallas", {"m": 4096, "k": 14336, "n": 4096}),
            ("gmm_quant.pallas", {"m": 4096, "k": 4096, "n": 14336})):
        assert registry.resolve(head, request).name == head


def test_no_request_resolves_the_flash_rung(record_property):
    """``attention.flash`` sits between splash and SDPA; its probe accepts a
    subset of what splash accepts, so on the chip nothing should reach it
    (ROADMAP Design 7 decides its fate from this)."""
    hits = []
    for s, d, cap, window, traced in itertools.product(
            (100, 128, 2048, 2176, 16384), (64, 80, 96, 128, 256),
            (False, True), (False, True), (False, True)):
        if traced and not window:
            continue
        request = {"q_seq": s, "kv_seq": s, "head_dim": d, "soft_cap": cap,
                   "window": window, "traced_window": traced}
        if registry.resolve("attention.splash",
                            request).name == "attention.flash":
            hits.append(request)
    record_property("flash_resolutions", len(hits))
    assert not hits, hits
