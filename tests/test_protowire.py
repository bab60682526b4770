"""Wire-format unit tests with hand-computed vectors, and the writer held
to a plain oracle of itself."""

import random
import struct

import pytest

from cometbft_tpu.libs import protowire as pw


def test_uvarint_roundtrip():
    for v in (0, 1, 127, 128, 300, 2 ** 32, 2 ** 64 - 1):
        enc = pw.encode_uvarint(v)
        dec, pos = pw.decode_uvarint(enc)
        assert dec == v and pos == len(enc)


def test_uvarint_known():
    assert pw.encode_uvarint(1) == b"\x01"
    assert pw.encode_uvarint(300) == b"\xac\x02"


def test_negative_int_is_ten_bytes():
    w = pw.Writer().int_field(1, -1)
    enc = w.bytes()
    # tag 0x08 + 10-byte varint of 2^64-1
    assert enc == b"\x08" + b"\xff" * 9 + b"\x01"
    r = pw.Reader(enc)
    f, wt = r.read_tag()
    assert (f, wt) == (1, pw.VARINT)
    assert r.read_int() == -1


def test_sfixed64():
    enc = pw.Writer().sfixed64_field(2, 1).bytes()
    assert enc == b"\x11\x01\x00\x00\x00\x00\x00\x00\x00"
    r = pw.Reader(enc)
    r.read_tag()
    assert r.read_sfixed64() == 1


def test_zero_scalars_omitted():
    w = (pw.Writer().int_field(1, 0).uvarint_field(2, 0)
         .bytes_field(3, b"").string_field(4, ""))
    assert w.bytes() == b""


def test_message_field_always_emitted():
    # gogo nullable=false: empty embedded message still writes tag+len
    assert pw.Writer().message_field(5, b"").bytes() == b"\x2a\x00"


def test_timestamp():
    enc = pw.encode_timestamp(5, 7)
    assert enc == b"\x08\x05\x10\x07"
    assert pw.decode_timestamp(enc) == (5, 7)
    assert pw.encode_timestamp(0, 0) == b""


def test_delimited():
    payload = b"hello"
    framed = pw.marshal_delimited(payload)
    assert framed == b"\x05hello"
    out, pos = pw.unmarshal_delimited(framed)
    assert out == payload and pos == len(framed)


def test_reader_skips_unknown():
    w = (pw.Writer().int_field(1, 9).bytes_field(2, b"xy")
         .sfixed64_field(3, 4).uvarint_field(4, 2))
    r = pw.Reader(w.bytes())
    seen = {}
    while not r.at_end():
        f, wt = r.read_tag()
        if f == 4:
            seen[f] = r.read_uvarint()
        else:
            r.skip(wt)
    assert seen == {4: 2}


# -- the oracle -------------------------------------------------------------
#
# `encode_uvarint` and `Writer` as they stood before the writer learnt to
# answer a one-byte varint and a tag from a table and to append a field at
# once: a loop for every varint, every field a chain of tag() and raw().
# Plain on purpose, and kept here: every input must give the same bytes
# through the shipped writer as through this one.

_U64 = (1 << 64) - 1


def oracle_encode_uvarint(v: int) -> bytes:
    if v < 0:
        raise ValueError("uvarint must be non-negative")
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


class OracleWriter:
    def __init__(self):
        self._parts = []

    def raw(self, b):
        self._parts.append(b)
        return self

    def tag(self, field, wire):
        self._parts.append(oracle_encode_uvarint((field << 3) | wire))
        return self

    def uvarint_field(self, field, v):
        if v != 0:
            self.tag(field, pw.VARINT).raw(oracle_encode_uvarint(v))
        return self

    def int_field(self, field, v):
        if v != 0:
            self.tag(field, pw.VARINT).raw(oracle_encode_uvarint(v & _U64))
        return self

    def bool_field(self, field, v):
        if v:
            self.tag(field, pw.VARINT).raw(b"\x01")
        return self

    def sfixed64_field(self, field, v):
        if v != 0:
            self.tag(field, pw.FIXED64).raw(struct.pack("<q", v))
        return self

    def bytes_field(self, field, v):
        if v:
            self.tag(field, pw.BYTES).raw(
                oracle_encode_uvarint(len(v))).raw(v)
        return self

    def string_field(self, field, v):
        return self.bytes_field(field, v.encode("utf-8"))

    def packed_uint64_field(self, field, vals):
        payload = b"".join(oracle_encode_uvarint(v & _U64) for v in vals)
        return self.bytes_field(field, payload)

    def message_field(self, field, payload):
        self.tag(field, pw.BYTES).raw(
            oracle_encode_uvarint(len(payload))).raw(payload)
        return self

    def optional_message_field(self, field, payload):
        if payload is not None:
            self.message_field(field, payload)
        return self

    def bytes(self):
        return b"".join(self._parts)


def oracle_marshal_delimited(payload: bytes) -> bytes:
    return oracle_encode_uvarint(len(payload)) + payload


def oracle_encode_timestamp(seconds: int, nanos: int) -> bytes:
    return OracleWriter().int_field(1, seconds).int_field(2, nanos).bytes()


def both(build) -> bytes:
    """What `build` writes through the shipped writer, held to what it
    writes through the oracle - twice, so that a table filled by the
    first pass answers the second."""
    want = build(OracleWriter()).bytes()
    for _ in range(2):
        assert build(pw.Writer()).bytes() == want
    return want


_RNG = random.Random(32)
EDGE_VARINTS = [0, 1, 127, 128, 129, 16383, 16384, 2 ** 32, 2 ** 63 - 1,
                2 ** 63, 2 ** 64 - 1]
# every byte length a varint can have, many times over
DRAWN_VARINTS = [_RNG.getrandbits(_RNG.randint(1, 64)) for _ in range(200)]
FIELDS = [1, 15, 16, 17, 2047, 2048, 2 ** 29 - 1]
WIRES = [pw.VARINT, pw.FIXED64, pw.BYTES, pw.FIXED32]
LENGTHS = [0, 1, 127, 128, 16383, 16384]
INTS = [1, -1, 127, 128, -128, 2 ** 31 - 1, -2 ** 31, 2 ** 63 - 1, -2 ** 63]


@pytest.mark.parametrize("v", EDGE_VARINTS + DRAWN_VARINTS)
def test_uvarint_is_the_oracles(v):
    enc = pw.encode_uvarint(v)
    assert enc == oracle_encode_uvarint(v)
    assert type(enc) is bytes
    assert pw.decode_uvarint(enc) == (v, len(enc))


@pytest.mark.parametrize("v", [-1, -128, -2 ** 63])
def test_negative_uvarint_raises(v):
    with pytest.raises(ValueError):
        pw.encode_uvarint(v)
    with pytest.raises(ValueError):
        pw.Writer().uvarint_field(1, v)


@pytest.mark.parametrize("wire", WIRES)
@pytest.mark.parametrize("field", FIELDS)
def test_tag_is_the_oracles(field, wire):
    r = pw.Reader(both(lambda w: w.tag(field, wire)))
    assert r.read_tag() == (field, wire) and r.at_end()


@pytest.mark.parametrize("field", FIELDS)
def test_a_fields_own_tag_is_the_oracles(field):
    # the tag each method writes by itself, no tag() in between
    both(lambda w: w.uvarint_field(field, 3).int_field(field, -3)
         .bool_field(field, True).sfixed64_field(field, 3)
         .bytes_field(field, b"x").string_field(field, "x")
         .packed_uint64_field(field, [3]).message_field(field, b"")
         .optional_message_field(field, b""))


def test_a_negative_field_raises_and_leaves_nothing_behind():
    for _ in range(2):
        with pytest.raises(ValueError):
            pw.Writer().tag(-1, pw.BYTES)
        with pytest.raises(ValueError):
            pw.Writer().bytes_field(-1, b"x")
        with pytest.raises(ValueError):
            pw.Writer().int_field(-1, 1)


ZEROS = [("uvarint_field", 0), ("int_field", 0), ("bool_field", False),
         ("sfixed64_field", 0), ("bytes_field", b""), ("string_field", ""),
         ("packed_uint64_field", []), ("optional_message_field", None)]


@pytest.mark.parametrize("method,zero", ZEROS,
                         ids=[m for m, _ in ZEROS])
@pytest.mark.parametrize("field", [1, 16])
def test_a_zero_is_omitted(method, zero, field):
    assert both(lambda w: getattr(w, method)(field, zero)) == b""


@pytest.mark.parametrize("n", LENGTHS)
@pytest.mark.parametrize("method", ["bytes_field", "message_field",
                                    "optional_message_field"])
@pytest.mark.parametrize("field", [1, 16])
def test_a_payload_of_every_length(method, n, field):
    payload = bytes(i & 0xFF for i in range(n))
    out = both(lambda w: getattr(w, method)(field, payload))
    if n == 0 and method == "bytes_field":
        assert out == b""
        return
    r = pw.Reader(out)
    assert r.read_tag() == (field, pw.BYTES)
    assert r.read_bytes() == payload and r.at_end()


@pytest.mark.parametrize("n", LENGTHS)
@pytest.mark.parametrize("char", ["a", "é", "世"])
def test_a_string_of_every_length(n, char):
    # the length written is that of the UTF-8 bytes, not of the string
    s = char * n
    out = both(lambda w: w.string_field(2, s))
    if n:
        r = pw.Reader(out)
        r.read_tag()
        assert r.read_string() == s and r.at_end()


@pytest.mark.parametrize("v", INTS + EDGE_VARINTS[1:9])
@pytest.mark.parametrize("field", [1, 16])
def test_int_field_is_the_oracles(field, v):
    out = both(lambda w: w.int_field(field, v))
    if v < 0:
        assert len(out) == len(pw.encode_uvarint(field << 3)) + 10
    r = pw.Reader(out)
    r.read_tag()
    assert r.read_int() == v and r.at_end()


@pytest.mark.parametrize("v", EDGE_VARINTS[1:] + DRAWN_VARINTS[:40])
def test_uvarint_field_is_the_oracles(v):
    out = both(lambda w: w.uvarint_field(3, v).uvarint_field(16, v))
    r = pw.Reader(out)
    r.read_tag()
    assert r.read_uvarint() == v


@pytest.mark.parametrize("v", [1, -1, 2 ** 63 - 1, -2 ** 63, 1 << 32])
def test_sfixed64_field_is_the_oracles(v):
    out = both(lambda w: w.sfixed64_field(1, v).sfixed64_field(16, v))
    r = pw.Reader(out)
    assert r.read_tag() == (1, pw.FIXED64)
    assert r.read_sfixed64() == v


@pytest.mark.parametrize("v", [True, 1, 2, "x"])
def test_bool_field_writes_one(v):
    assert both(lambda w: w.bool_field(1, v).bool_field(16, v)) == \
        b"\x08\x01\x80\x01\x01"


@pytest.mark.parametrize("vals", [
    [0], [1, 127, 128], [2 ** 64 - 1], [-1], list(range(200)),
    EDGE_VARINTS, DRAWN_VARINTS[:64]],
    ids=["zero", "small", "max", "negative", "range200", "edges", "drawn"])
def test_packed_uint64_field_is_the_oracles(vals):
    out = both(lambda w: w.packed_uint64_field(4, vals))
    r = pw.Reader(out)
    r.read_tag()
    assert r.read_packed_uint64() == [v & _U64 for v in vals]


def test_an_empty_message_is_written_and_a_missing_one_is_not():
    assert both(lambda w: w.message_field(5, b"")) == b"\x2a\x00"
    assert both(lambda w: w.optional_message_field(5, b"")) == b"\x2a\x00"
    assert both(lambda w: w.message_field(16, b"")) == b"\x82\x01\x00"
    assert both(lambda w: w.optional_message_field(5, None)) == b""


@pytest.mark.parametrize("n", LENGTHS)
def test_marshal_delimited_is_the_oracles(n):
    payload = b"\xa5" * n
    framed = pw.marshal_delimited(payload)
    assert framed == oracle_marshal_delimited(payload)
    assert pw.unmarshal_delimited(framed) == (payload, len(framed))
    assert pw.delimited_field_size(n) == \
        1 + len(oracle_encode_uvarint(n)) + n


@pytest.mark.parametrize("seconds,nanos", [
    (0, 0), (0, 1), (1, 0), (127, 127), (128, 128), (1_700_000_000, 0),
    (1_700_000_000, 999_999_999), (-1, 0), (-62135596800, 0), (0, -1),
    (2 ** 63 - 1, 2 ** 31 - 1)])
def test_encode_timestamp_is_the_oracles(seconds, nanos):
    enc = pw.encode_timestamp(seconds, nanos)
    assert enc == oracle_encode_timestamp(seconds, nanos)
    assert pw.decode_timestamp(enc) == (seconds, nanos)


def _drawn_message(rng: random.Random):
    """A message of drawn fields, as a list of (method, field, value)."""
    calls = []
    for _ in range(rng.randint(1, 24)):
        field = rng.choice(FIELDS + [2, 3, 4, 5, 9, 20, 21])
        kind = rng.randrange(8)
        small = rng.random() < 0.7
        if kind == 0:
            v = rng.randrange(128) if small else rng.getrandbits(
                rng.randint(8, 64))
            calls.append(("uvarint_field", field, v))
        elif kind == 1:
            v = rng.randrange(128) if small else rng.getrandbits(
                rng.randint(8, 63)) * rng.choice((1, -1))
            calls.append(("int_field", field, v))
        elif kind == 2:
            calls.append(("bool_field", field, rng.random() < 0.5))
        elif kind == 3:
            calls.append(("sfixed64_field", field,
                          rng.getrandbits(63) * rng.choice((0, 1, -1))))
        elif kind == 4:
            n = rng.randrange(128) if small else rng.randrange(128, 20000)
            calls.append(("bytes_field", field, rng.randbytes(n)))
        elif kind == 5:
            n = rng.randrange(128) if small else rng.randrange(128, 500)
            calls.append(("string_field", field,
                          "".join(rng.choice("aé世")
                                  for _ in range(n))))
        elif kind == 6:
            calls.append(("packed_uint64_field", field,
                          [rng.getrandbits(rng.randint(1, 64))
                           for _ in range(rng.randrange(6))]))
        else:
            n = rng.randrange(128) if small else rng.randrange(128, 20000)
            calls.append((rng.choice(("message_field",
                                      "optional_message_field")),
                          field, rng.randbytes(n)))
    return calls


@pytest.mark.parametrize("seed", range(48))
def test_a_drawn_message_is_the_oracles(seed):
    calls = _drawn_message(random.Random(3200 + seed))

    def build(w):
        for method, field, v in calls:
            assert getattr(w, method)(field, v) is w
        return w

    both(build)
