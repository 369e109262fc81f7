"""Every cell and configuration comes with the files the harness and the
CPU tests find by name, so that one left out fails here, by name, and not
as a ``KeyError`` inside a cell's test."""
import os

import chip_tiny
import pytest


def _chip(*parts):
    return os.path.join(chip_tiny.CHIP, *parts)


def _missing(override: dict, full: dict) -> list[str]:
    """Keys of ``override``, nested ones too, that ``full`` lacks."""
    out = []
    for k, v in override.items():
        if k not in full:
            out.append(k)
        elif isinstance(v, dict) and isinstance(full[k], dict):
            out += [f"{k}.{m}" for m in _missing(v, full[k])]
    return out


LISTED = [w["name"] for w in
          chip_tiny.load_json(os.path.join(chip_tiny.ROOT, "BENCHMARK.json"))["workloads"]]
FILES = chip_tiny.workload_files()
CONFIGS = sorted({chip_tiny.load_json(p)["config"] for p in FILES.values()})


@pytest.mark.parametrize("cell", LISTED)
def test_listed_cell_has_a_workload_file(cell):
    assert os.path.isfile(_chip("workloads", f"{cell}.json"))


@pytest.mark.parametrize("cell", sorted(FILES))
def test_workload_file_names_a_configuration_with_its_files(cell):
    config = chip_tiny.load_json(FILES[cell])["config"]
    for path in (_chip("configs", f"{config}.json"),
                 _chip("configs", f"{config}.py"),
                 _chip("tests", "tiny", f"{config}.json")):
        assert os.path.isfile(path), f"{cell}: {path} is missing"


@pytest.mark.parametrize("config", CONFIGS)
def test_tiny_size_overrides_only_keys_that_exist(config):
    size = chip_tiny.tiny(config)
    assert set(size) == {"config", "traffic"}
    assert _missing(size["config"],
                    chip_tiny.load_json(_chip("configs", f"{config}.json"))) == []
    for cell, path in FILES.items():
        traffic = chip_tiny.load_json(path)
        if traffic["config"] == config:
            assert _missing(size["traffic"], traffic) == [], cell


def test_missing_tiny_size_names_its_path():
    with pytest.raises(FileNotFoundError, match="no_such_config.json"):
        chip_tiny.tiny("no_such_config")
