"""Serving path: decode-position correctness, batch contract, the
continuous-batching engine, and the factorized-KV flash-decode kernel."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.factorized import factorize_params
from repro.models import model as M


def _greedy_reference(cfg, params, prompt, steps, extras, max_len):
    """Teacher-forced oracle: re-prefill prompt + generated-so-far each
    step.  Position bookkeeping is implicit in whole-prompt prefill, so
    this is immune to decode-position bugs."""
    toks = [int(t) for t in np.asarray(prompt)]
    out = []
    prefill = jax.jit(lambda p, b, c: M.prefill(p, cfg, b, c))
    for _ in range(steps):
        cache = M.init_cache(cfg, 1, max_len)
        batch = {"tokens": jnp.asarray([toks], jnp.int32), **extras}
        logits, _ = prefill(params, batch, cache)
        nxt = int(jnp.argmax(logits[0]))
        out.append(nxt)
        toks.append(nxt)
    return np.asarray(out, np.int32)


class TestDecodePositionRegression:
    def test_vision_decode_position(self):
        """Vision prefill writes num_patches extra cache positions before
        the tokens; decode must start at plen + num_patches.  The old
        ``pos = plen`` logic overwrote the cache mid-prompt — this test
        fails against it."""
        from repro.launch.serve import Server
        cfg = get_smoke_config("phi-3-vision-4.2b").replace(dtype="float32")
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        key = jax.random.PRNGKey(1)
        prompt = jax.random.randint(key, (10,), 0, cfg.vocab_size)
        patches = 0.02 * jax.random.normal(
            key, (1, cfg.num_patches, cfg.d_model))
        steps = 6
        want = _greedy_reference(cfg, params, prompt, steps,
                                 {"patches": patches}, max_len=64)
        srv = Server(cfg, params, max_len=64, batch=1)
        got = np.asarray(srv.generate(prompt[None], steps=steps,
                                      extras={"patches": patches}))[0]
        np.testing.assert_array_equal(got, want)

    def test_whisper_decode_position(self):
        """Audio frames fill the encoder cross-attn cache only — decoder
        self-attn prefill length stays at plen.  Parity guards against
        over-correcting the vision fix."""
        from repro.launch.serve import Server
        cfg = get_smoke_config("whisper-base").replace(dtype="float32")
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        key = jax.random.PRNGKey(1)
        prompt = jax.random.randint(key, (8,), 0, cfg.vocab_size)
        frames = 0.02 * jax.random.normal(
            key, (1, cfg.encoder_seq_len, cfg.d_model))
        steps = 5
        want = _greedy_reference(cfg, params, prompt, steps,
                                 {"frames": frames}, max_len=48)
        srv = Server(cfg, params, max_len=48, batch=1)
        got = np.asarray(srv.generate(prompt[None], steps=steps,
                                      extras={"frames": frames}))[0]
        np.testing.assert_array_equal(got, want)

    def test_vision_capacity_guard_counts_patches(self):
        """The max_len guard must count the patch positions prefill writes:
        plen + steps fits but patches + plen + steps does not."""
        from repro.launch.serve import Server
        cfg = get_smoke_config("phi-3-vision-4.2b").replace(dtype="float32")
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        key = jax.random.PRNGKey(1)
        prompts = jax.random.randint(key, (1, 10), 0, cfg.vocab_size)
        patches = 0.02 * jax.random.normal(
            key, (1, cfg.num_patches, cfg.d_model))
        srv = Server(cfg, params, max_len=30, batch=1)
        assert cfg.num_patches + 10 + 13 > 30 >= 10 + 13
        with pytest.raises(ValueError, match="max_len"):
            srv.generate(prompts, steps=13, extras={"patches": patches})


class TestBatchContract:
    def _server(self, batch):
        from repro.launch.serve import Server
        cfg = get_smoke_config("qwen3-0.6b").replace(dtype="float32")
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        return cfg, Server(cfg, params, max_len=48, batch=batch)

    def test_rejects_oversized_batch(self):
        cfg, srv = self._server(batch=2)
        prompts = jax.random.randint(jax.random.PRNGKey(1), (3, 8), 0,
                                     cfg.vocab_size)
        with pytest.raises(ValueError, match="batch"):
            srv.generate(prompts, steps=4)

    def test_pads_undersized_batch(self):
        """b < batch is padded to the slot count and sliced back — row i of
        a partial batch matches a full-batch generate of the same prompts."""
        cfg, srv = self._server(batch=4)
        prompts = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0,
                                     cfg.vocab_size)
        full = np.asarray(srv.generate(prompts, steps=5))
        part = srv.generate(prompts[:2], steps=5)
        assert part.shape == (2, 5)
        np.testing.assert_array_equal(np.asarray(part), full[:2])


class TestContinuousBatching:
    def _setup(self, arch="llama-7b", ratio=None):
        cfg = get_smoke_config(arch).replace(dtype="float32")
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        if ratio is not None:
            params = factorize_params(params, cfg, ratio=ratio)
        return cfg, params

    def test_slot_refill_preserves_state_fuzz(self):
        """Seeded fuzz over arrival orders / lengths: every request decoded
        through the shared-slot engine matches its single-request
        generation — finished-slot refills never corrupt neighbours."""
        from repro.launch.serve import (ContinuousBatchingServer, Request,
                                        Server)
        cfg, params = self._setup(ratio=0.5)
        rng = np.random.default_rng(0)
        single = Server(cfg, params, max_len=64, batch=1)
        for seed in range(3):
            order = rng.permutation(6)
            reqs = []
            for rid in order:
                plen = int(rng.integers(4, 14))
                steps = int(rng.integers(1, 9))
                prompt = rng.integers(0, cfg.vocab_size, size=(plen,),
                                      dtype=np.int32)
                reqs.append(Request(rid=int(rid), prompt=prompt,
                                    steps=steps))
            eng = ContinuousBatchingServer(cfg, params, max_len=64, slots=2)
            results = eng.run(reqs)
            assert sorted(results) == sorted(r.rid for r in reqs)
            for r in reqs:
                want = np.asarray(single.generate(
                    jnp.asarray(r.prompt)[None], steps=r.steps))[0]
                np.testing.assert_array_equal(
                    results[r.rid]["tokens"], want,
                    err_msg=f"seed {seed} rid {r.rid}")

    def test_chunked_prefill_matches_whole(self):
        """Chunk-by-chunk prefill produces the same logits as whole-prompt
        prefill — dense cache and factorized latent cache."""
        for ratio in (None, 0.5):
            cfg, params = self._setup(ratio=ratio)
            prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 12), 0,
                                        cfg.vocab_size)
            cache = M.init_cache(cfg, 1, 32, params=params)
            whole, _ = M.prefill(params, cfg, {"tokens": prompt}, cache)
            cache = M.init_cache(cfg, 1, 32, params=params)
            _, cache = M.prefill(params, cfg, {"tokens": prompt[:, :4]},
                                 cache, pos=0, chunked=True)
            _, cache = M.prefill(params, cfg, {"tokens": prompt[:, 4:8]},
                                 cache, pos=4, chunked=True)
            chunked, _ = M.prefill(params, cfg, {"tokens": prompt[:, 8:]},
                                   cache, pos=8, chunked=True)
            np.testing.assert_allclose(np.asarray(chunked),
                                       np.asarray(whole), atol=2e-4,
                                       rtol=2e-4)

    def test_latent_cache_matches_dense_decode(self):
        """Factorized-cache decode (in-kernel up-projection) matches the
        dense-cache decode of the SAME factorized params — one slot, and
        three slots at differing positions, where the scanned stage's
        latent cache is written and read in place at each layer."""
        from repro.launch.serve import ContinuousBatchingServer, Request
        cfg, params = self._setup(ratio=0.5)
        layouts = M.init_cache(cfg, 1, 32, params=params)
        assert any("lk" in c for st in layouts for c in st
                   if isinstance(c, dict)), "latent layout not engaged"
        rng = np.random.default_rng(3)
        for slots, plens in ((1, (10,)), (3, (10, 4, 17, 7))):
            reqs = [Request(rid=i, steps=8, prompt=rng.integers(
                        0, cfg.vocab_size, size=(n,), dtype=np.int32))
                    for i, n in enumerate(plens)]
            outs = {}
            for layout in ("auto", "dense"):
                eng = ContinuousBatchingServer(cfg, params, max_len=48,
                                               slots=slots,
                                               cache_layout=layout)
                res = eng.run(reqs)
                outs[layout] = [res[r.rid]["tokens"] for r in reqs]
            for got, want in zip(outs["auto"], outs["dense"]):
                np.testing.assert_array_equal(got, want,
                                              err_msg=f"slots {slots}")

    def test_latent_decode_keeps_cache_in_place(self, monkeypatch):
        """The lowered serve_step (the kernel in interpret mode, as on the
        chip) takes no whole layer of the stacked latent cache out of its
        stage: no pad of it, no dynamic slice of it, no dynamic update
        slice that writes one back — only per-slot blocks."""
        import functools
        import re
        from repro.kernels import ops as KO
        from repro.launch import steps as S
        cfg, params = self._setup(ratio=0.5)
        monkeypatch.setattr(KO, "flash_decode", functools.partial(
            KO.flash_decode, force_pallas=True, interpret=True))
        slots, max_len = 3, 48
        cache = M.init_cache(cfg, slots, max_len, params=params)
        latent = [c for c in cache[0] if "lk" in c]
        assert latent and latent[0]["lk"].shape[0] == cfg.num_layers
        layer = {c[k].size // cfg.num_layers
                 for c in latent for k in ("lk", "lv")}
        text = jax.jit(S.make_serve_step(cfg, None)).lower(
            params, cache, jnp.zeros((slots, 1), jnp.int32),
            jnp.asarray([3, 20, 41], jnp.int32)).as_text(dialect="hlo")
        size = {}
        ops = []
        for name, shape, op, args in re.findall(
                r"^\s*(?:ROOT )?(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\((.*?)\)",
                text, re.M):
            size[name] = int(np.prod([int(d) for d in shape.split(",")
                                      if d]))
            ops.append((name, op, [a.strip() for a in args.split(",")]))
        seen = []
        for name, op, args in ops:
            if op == "pad":
                seen.append((op, size.get(args[0])))
            elif op == "dynamic-slice":
                seen.append((op, size[name]))
            elif op == "dynamic-update-slice":
                seen.append((op, size.get(args[1])))
        assert any(op == "dynamic-update-slice" for op, _ in seen)
        whole = [(op, n) for op, n in seen if n in layer]
        assert not whole, f"whole-layer cache traffic in serve_step: {whole}"

    @pytest.mark.parametrize("arch,in_place", [("llama-7b", True),
                                               ("qwen3-0.6b", False)])
    def test_decode_inplace_layers(self, arch, in_place):
        """The engine counts, on its decode program's first trace, the
        attention layers whose latent cache it keeps in place: every layer
        of a latent config, none of a dense (qk_norm) one."""
        from repro.launch.serve import ContinuousBatchingServer, Request
        cfg, params = self._setup(arch, ratio=0.5)
        eng = ContinuousBatchingServer(cfg, params, max_len=32, slots=2)
        assert eng.decode_inplace_layers is None
        eng.run([Request(rid=0, prompt=np.arange(5, dtype=np.int32),
                         steps=3)])
        n = cfg.num_layers
        assert eng.decode_inplace_layers == ((n, 0) if in_place else (0, n))

    def test_poisson_arrivals_and_timestamps(self):
        """Requests arriving over time are admitted in order; timestamps
        are monotone per request."""
        from repro.launch.serve import ContinuousBatchingServer, Request
        cfg, params = self._setup()
        rng = np.random.default_rng(1)
        arrivals = np.cumsum(rng.exponential(0.01, size=4))
        reqs = [Request(rid=i, prompt=rng.integers(
                    0, cfg.vocab_size, size=(6,), dtype=np.int32),
                        steps=4, arrival=float(arrivals[i]))
                for i in range(4)]
        eng = ContinuousBatchingServer(cfg, params, max_len=32, slots=2)
        results = eng.run(reqs)
        assert len(results) == 4
        for i in range(4):
            r = results[i]
            assert r["tokens"].shape == (4,)
            assert (r["arrival"] <= r["admitted"] <= r["first_token"]
                    <= r["done"])
        assert len(eng.decode_step_times) >= 4


class TestFlashDecodeKernel:
    def _case(self, b, h, kv, d, l, rk, rv, seed=0):
        rng = np.random.default_rng(seed)
        f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
        q = f(b, h, d)
        lk, lv = f(b, l, rk), f(b, l, rv)
        uk = f(kv, rk, d) * 0.2
        uv = f(kv, rv, d) * 0.2
        lengths = jnp.asarray(rng.integers(1, l + 1, size=(b,)), jnp.int32)
        cos, sin = f(l, d // 2), f(l, d // 2)
        return q, lk, lv, uk, uv, lengths, cos, sin

    @staticmethod
    def _rank_major(args):
        """The case as the kernel takes it: its (B, L, r) latents in the
        cache's rank-major (B, r, L) layout, followed by the step's own
        latents, those already at lengths - 1 (so the kernel's write
        leaves the case as it is)."""
        q, lk, lv, uk, uv, lengths, cos, sin = args
        rows = jnp.arange(q.shape[0])
        return (q, jnp.swapaxes(lk, 1, 2), jnp.swapaxes(lv, 1, 2), uk, uv,
                lengths, cos, sin, lk[rows, lengths - 1],
                lv[rows, lengths - 1])

    @pytest.mark.parametrize("shape", [
        (2, 6, 2, 24, 40, 20, 12),    # unaligned head dim + ranks, GQA
        (1, 4, 4, 32, 64, 16, 16),    # MHA, aligned
        (3, 8, 2, 16, 48, 8, 24),     # asymmetric k/v ranks
    ])
    def test_kernel_matches_ref_interpret(self, shape):
        from repro.kernels import ref
        from repro.kernels.flash_decode import flash_decode
        args = self._case(*shape)
        want = ref.flash_decode_ref(*args)
        got, _, _ = flash_decode(*self._rank_major(args), bk=8,
                                 interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)

    def test_ops_wrapper_pads_and_dispatches(self):
        """The ops wrapper takes the (R, KV*D) param layout and the
        rank-major cache with unaligned ranks, and matches the reference
        on both the CPU and the interpret-mode Pallas path."""
        from repro.kernels import ops as KO, ref
        b, h, kv, d, l, rk, rv = 2, 6, 2, 24, 40, 20, 12
        args = self._case(b, h, kv, d, l, rk, rv, seed=7)
        q, lk, lv, uk, uv, lengths, cos, sin = args
        uk2 = jnp.transpose(uk, (1, 0, 2)).reshape(rk, kv * d)
        uv2 = jnp.transpose(uv, (1, 0, 2)).reshape(rv, kv * d)
        want = ref.flash_decode_ref(q, lk, lv, uk, uv, lengths, cos, sin)
        _, lk, lv, _, _, _, _, _, kn, vn = self._rank_major(args)
        cpu, _, _ = KO.flash_decode(q, lk, lv, uk2, uv2, lengths, cos, sin,
                                    kn, vn)
        np.testing.assert_allclose(np.asarray(cpu), np.asarray(want),
                                   atol=1e-6, rtol=1e-6)
        pal, _, _ = KO.flash_decode(q, lk, lv, uk2, uv2, lengths, cos, sin,
                                    kn, vn, force_pallas=True,
                                    interpret=True)
        np.testing.assert_allclose(np.asarray(pal), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)

    def test_norope_path(self):
        from repro.kernels import ref
        from repro.kernels.flash_decode import flash_decode
        args = self._case(2, 4, 2, 16, 32, 8, 8, seed=3)
        want = ref.flash_decode_ref(*args, rope=False)
        got, _, _ = flash_decode(*self._rank_major(args), use_rope=False,
                                 bk=8, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("path,l,layer", [
        ("kernel", 64, 0),
        ("kernel", 64, 2),
        ("kernel", 64, 3),
        ("ops", 64, 1),
        ("ops", 300, 2),              # L off the block grid: padded copy
    ])
    def test_stacked_cache_reads_layer_interpret(self, path, l, layer):
        """A stacked (n_layers, B, r, L) cache read at a layer index gives
        the reference's answer on that layer's unstacked slice, with the
        step's latents written at lengths - 1 of that layer and nowhere
        else; a rank off the lane grid (40) and per-slot lengths."""
        from repro.kernels import ops as KO, ref
        from repro.kernels.flash_decode import flash_decode
        n, b, h, kv, d, rk, rv = 4, 3, 8, 2, 32, 40, 24
        rng = np.random.default_rng(11)
        f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
        q, lk, lv = f(b, h, d), f(n, b, rk, l), f(n, b, rv, l)
        kn, vn = f(b, rk), f(b, rv)
        uk, uv = f(kv, rk, d) * 0.2, f(kv, rv, d) * 0.2
        lengths = jnp.asarray([1, l // 2 + 3, l], jnp.int32)
        cos, sin = f(l, d // 2), f(l, d // 2)
        rows = np.arange(b)
        want_lk = np.asarray(lk).copy()
        want_lv = np.asarray(lv).copy()
        want_lk[layer, rows, :, np.asarray(lengths) - 1] = np.asarray(kn)
        want_lv[layer, rows, :, np.asarray(lengths) - 1] = np.asarray(vn)
        want = ref.flash_decode_ref(
            q, jnp.swapaxes(want_lk[layer], 1, 2),
            jnp.swapaxes(want_lv[layer], 1, 2), uk, uv, lengths, cos, sin)
        if path == "kernel":
            got, got_lk, got_lv = flash_decode(
                q, lk, lv, uk, uv, lengths, cos, sin, kn, vn,
                jnp.int32(layer), bk=16, interpret=True)
        else:
            uk2 = jnp.transpose(uk, (1, 0, 2)).reshape(rk, kv * d)
            uv2 = jnp.transpose(uv, (1, 0, 2)).reshape(rv, kv * d)
            got, got_lk, got_lv = KO.flash_decode(
                q, lk, lv, uk2, uv2, lengths, cos, sin, kn, vn,
                layer=jnp.int32(layer), force_pallas=True, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(got_lk), want_lk)
        np.testing.assert_array_equal(np.asarray(got_lv), want_lv)
