"""Config parsing tests (reference config_test.go: defaults, env
override, strict mode)."""

import pytest

from veneur_tpu.core.config import Config, parse_duration, read_config


def test_defaults():
    c = read_config(data={})
    assert c.interval_seconds() == 10.0
    assert c.aggregates == ["min", "max", "count"]
    assert c.metric_max_length == 4096
    assert not c.is_local()


def test_yaml_file(tmp_path):
    p = tmp_path / "c.yaml"
    p.write_text("interval: 50ms\n"
                 "percentiles: [0.5, 0.9]\n"
                 "statsd_listen_addresses: ['udp://127.0.0.1:0']\n"
                 "forward_address: http://example:9000\n")
    c = read_config(str(p))
    assert c.interval_seconds() == pytest.approx(0.05)
    assert c.percentiles == [0.5, 0.9]
    assert c.is_local()


# a key the program never had, and one it had until its option went
# (PR 31: one producer out of the flush)
GONE_KEYS = [{"no_such_key": 1}, {"tpu_columnar_emit": False}]


@pytest.mark.parametrize("data", GONE_KEYS)
def test_unknown_key_warns_not_fails(data, caplog):
    with caplog.at_level("WARNING"):
        c = read_config(data=data)
    assert isinstance(c, Config)
    assert "unknown config keys" in caplog.text
    assert next(iter(data)) in caplog.text


@pytest.mark.parametrize("data", GONE_KEYS)
def test_unknown_key_strict_fails(data):
    with pytest.raises(ValueError, match="unknown config keys"):
        read_config(data=data, strict=True)


def test_env_override():
    c = read_config(data={}, env={"VENEUR_INTERVAL": "30s",
                                  "VENEUR_PERCENTILES": "0.5,0.99",
                                  "VENEUR_NUM_READERS": "4",
                                  "VENEUR_DEBUG_FLUSHED_METRICS": "true"})
    assert c.interval_seconds() == 30.0
    assert c.percentiles == [0.5, 0.99]
    assert c.num_readers == 4
    assert c.debug_flushed_metrics is True


@pytest.mark.parametrize("bad", [
    {"interval": "0s"},
    {"percentiles": [1.5]},
    {"aggregates": ["bogus"]},
    {"tpu_histo_rows": 0},
])
def test_validation_rejects(bad):
    with pytest.raises(ValueError):
        read_config(data=bad)


def test_parse_duration():
    assert parse_duration("10s") == 10.0
    assert parse_duration("50ms") == 0.05
    assert parse_duration("2m") == 120.0
    assert parse_duration(3) == 3.0
    with pytest.raises(ValueError):
        parse_duration("xx")


def test_env_override_dict_field():
    """VENEUR_* env overrides coerce dict-typed fields (the signalfx
    per-tag API-key map) from "k1:v1,k2:v2" form."""
    from veneur_tpu.core.config import read_config
    c = read_config(data={"interval": "10s"}, env={
        "VENEUR_SIGNALFX_PER_TAG_API_KEYS": "infra:tok1, web:tok2"})
    assert c.signalfx_per_tag_api_keys == {"infra": "tok1",
                                           "web": "tok2"}


def test_kafka_serialization_format_validated():
    from veneur_tpu.core.config import read_config
    with pytest.raises(ValueError, match="serialization"):
        read_config(data={"interval": "10s",
                          "kafka_span_serialization_format": "avro"})
