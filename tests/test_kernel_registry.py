"""Kernel-backend conformance (DESIGN.md §Kernels): every registered
AdapterMethod × every available backend must agree with its einsum reference
— forward and gradient — through the same `AdapterMethod` dispatch the
train/serve/merge hot paths use. Plus the policy layer: capability fallback
(vocab dims), build-time resolution snapshots, and the `use_pallas`
deprecation shim."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.configs.base import PEFTConfig
from repro.core import adapter as adapter_api
from repro.core.adapter import AdapterSite
from repro.kernels import api
from repro.models import build

SITE = AdapterSite("layers/wq", 96, 160, 2)

PARAM_METHODS = adapter_api.registered_methods(site_params_only=True)

# backends worth cross-checking against einsum on this host: interpret
# everywhere, compiled pallas only where it can actually run
ALT_BACKENDS = ("interpret", "pallas") if jax.default_backend() == "tpu" \
    else ("interpret",)


def _peft(method: str, backend: str = "auto") -> PEFTConfig:
    return PEFTConfig(method=method, n=24, alpha=25.0, lora_r=2,
                      param_dtype="float32", kernel_backend=backend)


def _randomized_site(method: str, site=SITE, seed=0):
    m = adapter_api.resolve(method)
    peft = _peft(method)
    ad = m.init_site(jax.random.PRNGKey(seed), site, peft)
    ad = {k: (v + 0.05 * jax.random.normal(jax.random.PRNGKey(i + seed + 1),
                                           v.shape)
              if jnp.issubdtype(v.dtype, jnp.floating) else v)
          for i, (k, v) in enumerate(ad.items())}
    return m, ad


def _alt_backends(method: str, op: str, d1=SITE.d_in, d2=SITE.d_out):
    """Alternative backends that both exist and would actually be selected
    for this (method, op, dims) on this host."""
    out = []
    for b in ALT_BACKENDS:
        chosen = api.resolve_op(op, method, _peft(method, b), d1, d2,
                                missing_ok=True)
        if chosen is not None and chosen.backend == b:
            out.append(b)
    return out


class TestBackendParity:
    @pytest.mark.parametrize("method", PARAM_METHODS)
    def test_site_delta_backends_agree(self, method):
        m, ad = _randomized_site(method)
        if "deltaw" not in api.ops_for(m):
            return
        dw_ref = m.site_delta(ad, SITE, _peft(method, "einsum"))
        for b in _alt_backends(method, "deltaw"):
            dw = m.site_delta(ad, SITE, _peft(method, b))
            np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_ref),
                                       atol=2e-5, rtol=1e-5,
                                       err_msg=f"{method}/{b}")

    @pytest.mark.parametrize("method", PARAM_METHODS)
    def test_factored_apply_backends_agree(self, method):
        m, ad = _randomized_site(method)
        tr, aux = m.split_adapter({k: v[0] for k, v in ad.items()
                                   if k in m.trainable_leaves(_peft(method))}
                                  | {k: v for k, v in ad.items()
                                     if k not in m.trainable_leaves(
                                         _peft(method))}, _peft(method))
        x = jax.random.normal(jax.random.PRNGKey(7), (5, SITE.d_in))
        y_ref = m.factored_apply(x, tr, aux, SITE.d_in, SITE.d_out,
                                 _peft(method, "einsum"))
        for b in _alt_backends(method, "factored_apply"):
            y = m.factored_apply(x, tr, aux, SITE.d_in, SITE.d_out,
                                 _peft(method, b))
            np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                       atol=2e-5, rtol=1e-5,
                                       err_msg=f"{method}/{b}")

    @pytest.mark.parametrize("method", PARAM_METHODS)
    def test_bank_apply_backends_agree(self, method):
        m, _ = _randomized_site(method)
        names = m.trainable_leaves(_peft(method))
        rows = [_randomized_site(method, seed=s)[1] for s in range(3)]
        aux = {k: v for k, v in rows[0].items() if k not in names}
        tr = {k: jnp.stack([r[k][0] for r in rows]) for k in names}
        x = jax.random.normal(jax.random.PRNGKey(9), (3, 4, SITE.d_in))
        y_ref = m.bank_apply(x, tr, aux, SITE.d_in, SITE.d_out,
                             _peft(method, "einsum"))
        for b in _alt_backends(method, "bank_apply"):
            y = m.bank_apply(x, tr, aux, SITE.d_in, SITE.d_out,
                             _peft(method, b))
            np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                       atol=2e-5, rtol=1e-5,
                                       err_msg=f"{method}/{b}")
            # zero trainables stay exactly zero on every backend (the
            # adapter bank's reserved-row contract)
            zero = {k: jnp.zeros_like(v) for k, v in tr.items()}
            yz = m.bank_apply(x, zero, aux, SITE.d_in, SITE.d_out,
                              _peft(method, b))
            assert not np.any(np.asarray(yz)), f"{method}/{b}"

    @pytest.mark.parametrize("method", PARAM_METHODS)
    def test_gradcheck_backends_agree(self, method):
        """d(loss)/d(trainables) through site_delta (stacked, the merged
        train path — exercises the custom-VJP dc kernels under vmap) and
        through factored_apply must match the einsum gradients."""
        m, ad = _randomized_site(method)
        names = m.trainable_leaves(_peft(method))

        if "deltaw" in api.ops_for(m):
            g = jax.random.normal(jax.random.PRNGKey(3),
                                  (SITE.stack, SITE.d_in, SITE.d_out))

            def loss_delta(tr, peft):
                return jnp.vdot(g, m.site_delta({**ad, **tr}, SITE, peft))

            tr0 = {k: ad[k] for k in names}
            g_ref = jax.grad(loss_delta)(tr0, _peft(method, "einsum"))
            for b in _alt_backends(method, "deltaw"):
                g_b = jax.grad(loss_delta)(tr0, _peft(method, b))
                for k in g_ref:
                    np.testing.assert_allclose(
                        np.asarray(g_b[k]), np.asarray(g_ref[k]),
                        atol=1e-4, rtol=1e-3, err_msg=f"{method}/{b}/{k}")

        x = jax.random.normal(jax.random.PRNGKey(4), (5, SITE.d_in))
        aux = {k: v for k, v in ad.items() if k not in names}
        tr0 = {k: ad[k][0] for k in names}

        def loss_fact(tr, peft):
            return jnp.sum(m.factored_apply(x, tr, aux, SITE.d_in,
                                            SITE.d_out, peft) ** 2)

        g_ref = jax.grad(loss_fact)(tr0, _peft(method, "einsum"))
        for b in _alt_backends(method, "factored_apply"):
            g_b = jax.grad(loss_fact)(tr0, _peft(method, b))
            for k in g_ref:
                np.testing.assert_allclose(
                    np.asarray(g_b[k]), np.asarray(g_ref[k]),
                    atol=1e-4, rtol=1e-3, err_msg=f"{method}/{b}/{k}")

    def test_every_dispatched_op_has_einsum_reference(self):
        """The terminal fallback must exist for every op a method serves."""
        for method in PARAM_METHODS:
            for op in api.ops_for(method):
                assert api.lookup(op, method, "einsum") is not None, \
                    (method, op)


class TestCapabilityFallback:
    def test_vocab_dim_routes_to_einsum(self):
        """> int32-phase-bound dims (embedding/vocab grids) fall off the
        Pallas path even when explicitly requested — per-op bounds."""
        for method, safe in (("fourierft", 46336), ("dct", 32500)):
            peft = _peft(method, "interpret")
            assert api.resolve_op("deltaw", method, peft, 152064,
                                  4096).backend == "einsum"
            assert api.resolve_op("deltaw", method, peft, safe,
                                  128).backend == "interpret"
            assert api.resolve_op("deltaw", method, peft, safe + 1,
                                  128).backend == "einsum"

    def test_compiled_pallas_needs_tpu(self):
        if jax.default_backend() == "tpu":
            pytest.skip("compiled path IS available here")
        peft = _peft("fourierft", "auto")
        assert api.resolve_op("deltaw", "fourierft", peft, 256,
                              256).backend == "einsum"
        assert api.resolve_op("deltaw", "fourierft", peft, 256, 256,
                              platform="tpu").backend == "pallas"

    def test_non_fourier_basis_uses_einsum(self):
        """Table-6 ablation bases have no integer-phase structure — the
        config predicate keeps them off the Pallas path."""
        peft = _peft("fourierft", "interpret").replace(basis="random")
        assert api.resolve_op("deltaw", "fourierft", peft, 256,
                              256).backend == "einsum"

    def test_unknown_backend_rejected(self):
        with pytest.raises(ValueError, match="kernel_backend"):
            PEFTConfig(kernel_backend="cuda")
        with pytest.raises(ValueError, match="kernel backend"):
            api.resolve_op("deltaw", "fourierft", None, 8, 8,
                           backend="cuda")


class TestKernelPolicy:
    def test_model_policy_snapshot_and_explain(self):
        cfg = C.reduced(C.get("yi-6b")).replace(vocab=64)
        model = build(cfg, _peft("fourierft", "interpret"))
        pol = model.kernel_policy.validate()
        assert pol.method == "fourierft" and pol.requested == "interpret"
        assert {r.op for r in pol.resolutions} == {"deltaw", "factored_apply",
                                                   "bank_apply",
                                                   "paged_attention"}
        assert pol.backend_for("layers/wq", "deltaw") == "interpret"
        assert pol.backend_for("attention", "paged_attention") == "interpret"
        text = model.explain_kernels()
        assert "layers/wq" in text and "deltaw -> interpret" in text
        assert "paged_attention -> interpret" in text

    def test_explicit_pallas_downgrade_warns(self):
        """An explicit pallas request that cannot be honoured off-TPU is an
        error at model build, never a silent (or merely warned) einsum
        downgrade."""
        if jax.default_backend() == "tpu":
            pytest.skip("no downgrade on TPU")
        cfg = C.reduced(C.get("yi-6b")).replace(vocab=64)
        with pytest.raises(api.KernelUnavailableError,
                           match="pallas.*cannot run"):
            build(cfg, _peft("fourierft", "pallas"))

    def test_stateless_methods_have_empty_policy(self):
        cfg = C.reduced(C.get("yi-6b")).replace(vocab=64)
        for name in ("none", "full"):
            model = build(cfg, PEFTConfig(method=name))
            # only the model-side paged_attention op: no adapter site ops
            assert [r.op for r in model.kernel_policy.resolutions] \
                == ["paged_attention"]
            assert "no registered kernel ops" in model.explain_kernels()


class TestHotPathDispatch:
    """End to end: merged (site_delta through the Pallas interpret harness)
    == factored (einsum bypass) through a real model forward, for every
    spectral method — the acceptance gate for train/serve wiring."""

    @pytest.mark.parametrize("method", ["fourierft", "dct", "circulant"])
    def test_interpret_forward_matches_einsum(self, method):
        cfg = C.reduced(C.get("yi-6b")).replace(vocab=64,
                                                param_dtype="float32",
                                                dtype="float32")
        peft = _peft(method, "einsum")
        model_e = build(cfg, peft)
        params = model_e.init(jax.random.PRNGKey(0))
        params["peft"] = jax.tree.map(
            lambda x: x + 0.03 if jnp.issubdtype(x.dtype, jnp.floating) else x,
            params["peft"])
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 10),
                                              0, 64)}
        ref_logits, _ = model_e.forward(params, batch)
        for strategy in ("merged", "factored"):
            model_i = build(cfg, peft.replace(kernel_backend="interpret",
                                              strategy=strategy))
            got, _ = model_i.forward(params, batch)
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref_logits),
                                       atol=5e-4, rtol=1e-3,
                                       err_msg=f"{method}/{strategy}")

    def test_train_step_grads_through_interpret_kernels(self):
        """One real train step (merged strategy) with the interpret backend:
        the dc VJP kernel feeds the optimizer, matching einsum grads."""
        from repro.configs.base import TrainConfig
        from repro.train import step as train_step
        cfg = C.reduced(C.get("yi-6b"), layers=2, width=64).replace(
            vocab=32, param_dtype="float32", dtype="float32")
        tcfg = TrainConfig(total_steps=2, warmup_steps=1)
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(0), (2, 8),
                                              0, 32),
                 "labels": jax.random.randint(jax.random.PRNGKey(1), (2, 8),
                                              0, 32)}
        metrics = {}
        for backend in ("einsum", "interpret"):
            model = build(cfg, _peft("fourierft", backend))
            state, frozen = train_step.init_state(model, tcfg,
                                                  jax.random.PRNGKey(2))
            step = train_step.make_train_step(model, tcfg)
            _, m = step(state, frozen, batch)
            metrics[backend] = m
        np.testing.assert_allclose(float(metrics["interpret"]["loss"]),
                                   float(metrics["einsum"]["loss"]),
                                   rtol=1e-5)
        np.testing.assert_allclose(float(metrics["interpret"]["grad_norm"]),
                                   float(metrics["einsum"]["grad_norm"]),
                                   rtol=1e-3)


class TestLegacyShim:
    def test_use_pallas_maps_to_kernel_backend(self):
        for legacy, backend in (("auto", "auto"), ("never", "einsum"),
                                ("interpret", "interpret")):
            with pytest.warns(DeprecationWarning, match="use_pallas"):
                p = PEFTConfig(use_pallas=legacy)
            assert p.kernel_backend == backend
            assert p.use_pallas is None
            # replace() must not re-warn or lose the mapping
            assert p.replace(n=7).kernel_backend == backend

    def test_bad_use_pallas_rejected(self):
        with pytest.raises(ValueError, match="use_pallas"):
            PEFTConfig(use_pallas="always")

    def test_profile_key_ignores_kernel_backend(self):
        """Serving bank admission must not refuse tenants trained under a
        different kernel backend — same math, different implementation."""
        from repro.serve.engine import AdapterBank
        key = lambda p: AdapterBank._profile_key(AdapterBank, p)
        assert key(_peft("fourierft", "auto")) \
            == key(_peft("fourierft", "interpret"))
        assert key(_peft("fourierft")) != key(_peft("fourierft").replace(n=9))

    def test_old_manifest_migrates_silently(self, tmp_path):
        """Adapter exports written before the registry carry use_pallas;
        import maps it onto kernel_backend without a deprecation warning."""
        import warnings
        from repro.checkpoint import adapters as ckpt
        m, ad = _randomized_site("fourierft")
        ckpt.export_adapter(str(tmp_path), "t0", {"layers/wq": ad},
                            _peft("fourierft"))
        mpath = os.path.join(str(tmp_path), "t0", "manifest.json")
        with open(mpath) as f:
            manifest = json.load(f)
        manifest["peft"].pop("kernel_backend")
        manifest["peft"]["use_pallas"] = "never"
        with open(mpath, "w") as f:
            json.dump(manifest, f)
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            peft = ckpt.read_manifest(str(tmp_path), "t0")
        assert peft.kernel_backend == "einsum" and peft.use_pallas is None
