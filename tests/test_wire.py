"""Wire codec + transports (DESIGN.md §11).

The serialized upload is the unit the fault engine corrupts, drops and
retries, so the codec's contract is load-bearing:

* round-trip exactness — indices and values come back bit-identical,
  for f32 and bf16 value payloads;
* packing — the index bitpacking gives, byte for byte, what the original
  per-bit packer gave (kept below as the oracle) at every width 1-32;
* payload size — ``payload_nbytes`` is EXACT (header + ceil(log2 n)-bit
  packed indices + values + CRC-32), since modeled traffic accounting
  and the measured wire bytes must agree;
* corruption detection — every single-bit flip anywhere in the payload
  raises ``WireCRCError`` (flips inside the CRC field included);
* malformed-header rejection — magic/version/length mismatches raise
  ``WireFormatError``, never garbage uploads;
* transports — loopback preserves order; the multiprocessing queue
  transport delivers every payload across a real process boundary.
"""
import numpy as np
import pytest

from repro.core import rng as RNG
from repro.fl import faults as F
from repro.fl import wire as W


def _upload(n_params=1000, k=37, seed=3, dtype="float32"):
    rng = RNG.stream(seed, RNG.KIND_FAULTS, 99)
    idx = np.sort(rng.choice(n_params, size=k, replace=False)).astype(
        np.int64)
    vals = rng.normal(0, 1.0, size=k).astype(np.float32)
    payload = W.encode_upload(idx, vals, client=7, round_=5,
                              n_params=n_params, value_dtype=dtype)
    return idx, vals, payload


def _pack_oracle(indices, width):
    """The original packer: a [k, width] matrix of single bits, packed."""
    idx = np.asarray(indices, np.uint64)
    shifts = np.arange(width - 1, -1, -1, dtype=np.uint64)
    bits = ((idx[:, None] >> shifts) & np.uint64(1)).astype(np.uint8)
    return np.packbits(bits.ravel()).tobytes()


def _unpack_oracle(buf, k, width):
    bits = np.unpackbits(np.frombuffer(buf, np.uint8), count=k * width)
    bits = bits.reshape(k, width).astype(np.uint64)
    shifts = np.arange(width - 1, -1, -1, dtype=np.uint64)
    return (bits << shifts).sum(axis=1).astype(np.int32)


class TestIndexPacking:
    @pytest.mark.parametrize("k", [0, 1, 7, 8, 9, 1000])
    @pytest.mark.parametrize("width", range(1, 33))
    def test_matches_the_per_bit_oracle(self, width, k):
        top = (1 << width) - 1
        rng = RNG.stream(width, RNG.KIND_FAULTS, 98, k)
        idx = rng.integers(0, top, size=k, endpoint=True, dtype=np.int64)
        idx[:2] = [0, top][:k]
        packed = W._pack_indices(idx, width)
        assert packed == _pack_oracle(idx, width)
        assert len(packed) == (k * width + 7) // 8
        got = W._unpack_indices(packed, k, width)
        assert got.dtype == np.int32
        np.testing.assert_array_equal(got, _unpack_oracle(packed, k, width))
        # encode keeps the low ``width`` bits of an index past the width
        high = idx + (rng.integers(1, 8, size=k) << width)
        assert W._pack_indices(high, width) == _pack_oracle(high, width)

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("k", [16_413, 98_480])
    def test_har_sized_round_trip(self, k, dtype, monkeypatch):
        # the HAR CNN's 164,134 parameters (18-bit indices) at theta_u
        # 0.1 and 0.6
        n_params = 164_134
        idx, vals, payload = _upload(n_params=n_params, k=k, dtype=dtype)
        assert len(payload) == W.payload_nbytes(n_params, k, dtype)
        u = W.decode_upload(payload)
        np.testing.assert_array_equal(u.indices, idx)
        expect = (vals if dtype == "float32" else
                  W.bf16_bytes_to_f32(W.f32_to_bf16_bytes(vals)))
        np.testing.assert_array_equal(u.values, expect)
        monkeypatch.setattr(W, "_pack_indices", _pack_oracle)
        assert payload == _upload(n_params=n_params, k=k, dtype=dtype)[2]


class TestCodec:
    def test_f32_round_trip_bit_exact(self):
        idx, vals, payload = _upload()
        u = W.decode_upload(payload)
        assert (u.client, u.round, u.n_params) == (7, 5, 1000)
        np.testing.assert_array_equal(u.indices, idx)
        np.testing.assert_array_equal(u.values, vals)

    def test_bf16_round_trip(self):
        idx, vals, payload = _upload(dtype="bfloat16")
        u = W.decode_upload(payload)
        np.testing.assert_array_equal(u.indices, idx)
        # bf16 on the wire is TRUNCATING (round-to-zero: drop the low
        # mantissa half) — decoded f32 must match that exactly, and the
        # low 16 bits of every decoded value must be zero
        expect = np.asarray(W.bf16_bytes_to_f32(W.f32_to_bf16_bytes(vals)))
        np.testing.assert_array_equal(u.values, expect)
        assert (u.values.view(np.uint32) & 0xFFFF == 0).all()
        # truncation error is bounded by one bf16 ulp (2^-7 relative)
        np.testing.assert_allclose(u.values, vals, rtol=2 ** -7)

    def test_payload_nbytes_exact(self):
        for n_params, k in [(1000, 37), (1 << 17, 1), (130, 130), (2, 1)]:
            _, _, payload = _upload(n_params=n_params, k=k)
            assert len(payload) == W.payload_nbytes(n_params, k)

    def test_empty_upload(self):
        payload = W.encode_upload(np.zeros(0, np.int64),
                                  np.zeros(0, np.float32),
                                  client=0, round_=0, n_params=10)
        u = W.decode_upload(payload)
        assert len(u.indices) == 0 and len(u.values) == 0

    def test_densify(self):
        idx, vals, payload = _upload(n_params=50, k=5)
        dense = W.decode_upload(payload).densify()
        assert dense.shape == (50,)
        np.testing.assert_array_equal(dense[idx], vals)
        mask = np.ones(50, bool)
        mask[idx] = False
        assert (dense[mask] == 0).all()

    def test_index_out_of_range_rejected(self):
        # 1000 fits in idx_bits(1000)=10 bits, so it survives packing —
        # the decoder must still reject it against n_params
        payload = W.encode_upload(np.array([1000]), np.ones(1, np.float32),
                                  client=0, round_=0, n_params=1000)
        with pytest.raises(W.WireFormatError):
            W.decode_upload(payload)


class TestCorruptionDetection:
    def test_every_single_bit_flip_is_caught(self):
        _, _, payload = _upload(n_params=64, k=9)
        for byte in range(len(payload)):
            for bit in range(8):
                bad = bytearray(payload)
                bad[byte] ^= 1 << bit
                with pytest.raises((W.WireCRCError, W.WireFormatError)):
                    W.decode_upload(bytes(bad))

    def test_flip_bit_deterministic_and_caught(self):
        cfg_seed = 11
        _, _, payload = _upload()
        a = F.flip_bit(payload, cfg_seed, 3, 7, salt=0)
        b = F.flip_bit(payload, cfg_seed, 3, 7, salt=0)
        assert a == b and a != payload
        assert F.flip_bit(payload, cfg_seed, 3, 7, salt=1) != a
        with pytest.raises(W.WireCRCError):
            W.decode_upload(a)

    def test_truncated_payload_rejected(self):
        _, _, payload = _upload()
        with pytest.raises(W.WireError):
            W.decode_upload(payload[:-3])
        with pytest.raises(W.WireError):
            W.decode_upload(payload[:10])

    def test_wrong_magic_rejected(self):
        # recompute the CRC over the tampered body: the format check, not
        # the integrity check, must reject a well-checksummed alien frame
        import struct
        import zlib
        _, _, payload = _upload()
        body = b"XX" + payload[2:-W.CRC_BYTES]
        bad = body + struct.pack("<I", zlib.crc32(body))
        with pytest.raises(W.WireFormatError):
            W.decode_upload(bad)


class TestTransports:
    def test_loopback_preserves_order(self):
        tr = W.LoopbackTransport()
        payloads = [_upload(seed=s)[2] for s in range(5)]
        for p in payloads:
            tr.send(p)
        assert tr.drain() == payloads
        assert tr.drain() == []
        tr.close()

    def test_queue_transport_delivers_across_processes(self):
        tr = W.QueueTransport()
        payloads = [_upload(seed=s)[2] for s in range(4)]
        for p in payloads:
            tr.send(p)
        got = tr.drain(len(payloads), timeout=60)
        assert sorted(got) == sorted(payloads)
        tr.close()

    def test_make_transport(self):
        assert isinstance(W.make_transport("loopback"),
                          W.LoopbackTransport)
        with pytest.raises(ValueError):
            W.make_transport("carrier_pigeon")
