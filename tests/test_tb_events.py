"""TensorBoard event-file writer (utils/tb_events.py): CRC-verified
round-trip through the in-tree reader, plus known crc32c vectors so the
framing matches TensorFlow's TFRecord format exactly (no tensorboard
install exists here to cross-check against — the CRC vectors and the
proto layout ARE the compatibility contract)."""

import os

from fast_autoaugment_tpu.utils.logging import ScalarWriter, TeeWriter, make_writers
from fast_autoaugment_tpu.utils.tb_events import TBEventWriter, crc32c, read_events


def test_crc32c_known_vectors():
    # RFC 3720 / kernel test vectors for CRC-32C (Castagnoli)
    assert crc32c(b"") == 0x00000000
    assert crc32c(b"123456789") == 0xE3069283
    assert crc32c(b"a") == 0xC1D04330
    assert crc32c(bytes(32)) == 0x8A9136AA


def test_event_file_round_trip(tmp_path):
    w = TBEventWriter(str(tmp_path), "train")
    w.add_scalar("loss", 1.5, step=1)
    w.add_scalar("top1", 0.25, step=2)
    w.close()

    events = read_events(w.path)  # CRC-verified parse
    assert events[0]["file_version"] == "brain.Event:2"
    scalars = [(e["tag"], round(e["value"], 6), e.get("step"))
               for e in events[1:]]
    assert scalars == [("loss", 1.5, 1), ("top1", 0.25, 2)]
    assert all(e["wall_time"] > 0 for e in events)


def test_make_writers_tb_opt_in(tmp_path):
    train, valid, test = make_writers(str(tmp_path), "run", True, tb=True)
    assert isinstance(train, TeeWriter)
    train.add_scalar("loss", 2.0, step=1)
    train.flush()
    # JSONL sidecar still written
    assert os.path.exists(os.path.join(tmp_path, "run_train.jsonl"))
    # and a tfevents file per split under tb/
    tb_dir = os.path.join(tmp_path, "tb", "run_train")
    files = os.listdir(tb_dir)
    assert len(files) == 1 and files[0].startswith("events.out.tfevents.")
    events = read_events(os.path.join(tb_dir, files[0]))
    assert events[1]["tag"] == "loss" and events[1]["value"] == 2.0
    for w in (train, valid, test):
        w.close()

    # default stays JSONL-only (no tb/ churn in search sidecar flows)
    w2 = make_writers(str(tmp_path / "plain"), "run", True)[0]
    assert isinstance(w2, ScalarWriter)
    w2.close()


def test_two_writers_same_second_get_distinct_files(tmp_path):
    """Same logdir/name within one second must not interleave two
    streams in one file (ADVICE r4): exclusive create + numbered retry."""
    w1 = TBEventWriter(str(tmp_path), "train")
    w2 = TBEventWriter(str(tmp_path), "train")
    try:
        assert w1.path != w2.path
        w1.add_scalar("a", 1.0, 0)
        w2.add_scalar("a", 2.0, 0)
    finally:
        w1.close()
        w2.close()
    # each file parses standalone with exactly one file_version record
    for p in (w1.path, w2.path):
        events = read_events(p)
        assert sum("file_version" in e for e in events) == 1


def test_reader_crc_mismatch_raises_value_error(tmp_path):
    """CRC failures must raise ValueError, not assert (python -O strips
    asserts, silently voiding verify_crc=True) — ADVICE r4."""
    import pytest

    w = TBEventWriter(str(tmp_path), "train")
    w.add_scalar("loss", 1.5, step=1)
    w.close()
    data = bytearray(open(w.path, "rb").read())
    data[12] ^= 0xFF  # first payload byte of the file_version record
    with open(w.path, "wb") as fh:
        fh.write(bytes(data))
    with pytest.raises(ValueError, match="crc mismatch"):
        read_events(w.path)
    # opting out of verification still parses the frames
    assert read_events(w.path, verify_crc=False)


def test_reader_raises_value_error_on_any_malformed_frame(tmp_path):
    """Whatever bytes a damaged frame holds (a payload read as fields runs
    by the bytes of the writer's clock), the reader raises ``ValueError``
    and nothing else; without verification it keeps the frame's place and
    goes on."""
    import pytest

    from fast_autoaugment_tpu.utils.tb_events import _event_bytes, _record

    good = _record(_event_bytes(1.0, step=3, scalar=("loss", 2.0)))
    # intact CRCs around payloads that are no Event message: a key whose
    # varint never ends, a string that is not UTF-8, a double cut short
    for i, payload in enumerate((b"\xf6\xff\xff", b"\x1a\x02\xff\xfe",
                                 b"\x09\x00")):
        path = str(tmp_path / f"events.{i}")
        with open(path, "wb") as fh:
            fh.write(_record(payload) + good)
        with pytest.raises(ValueError, match="malformed frame @ 0"):
            read_events(path)
        events = read_events(path, verify_crc=False)
        assert events[0] == {} and events[1]["tag"] == "loss"
    # every damaged byte of a real file: ValueError or a clean parse
    w = TBEventWriter(str(tmp_path / "real"), "train")
    w.add_scalar("loss", 1.5, step=1)
    w.close()
    data = open(w.path, "rb").read()
    for pos in range(len(data)):
        for flip in (0xFF, 0x80, 0x01):
            broken = bytearray(data)
            broken[pos] ^= flip
            with open(w.path, "wb") as fh:
                fh.write(bytes(broken))
            for verify in (True, False):
                try:
                    read_events(w.path, verify_crc=verify)
                except ValueError:
                    pass
