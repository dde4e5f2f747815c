"""``spec``'s lookups by name: the architecture of a ``model_type`` and
the kernel families of added files."""

import json
import shutil

import pytest

from bench import spec


@pytest.mark.parametrize("model_type", ["qwen2", "minicpm"])
def test_the_dense_types_share_one_module(model_type):
    from bench.archs import _dense
    arch = spec.arch(model_type)
    assert arch is spec.arch(model_type)
    assert arch.serving_loader is _dense.serving_loader
    assert arch.reference is _dense.reference


@pytest.mark.parametrize("path", sorted((spec.BENCH / "configs").glob(
    "*.json")), ids=lambda p: p.stem)
def test_every_configuration_finds_its_architecture(path):
    conf = json.loads(path.read_text())
    cfg = spec.arch(conf["model_type"]).arch_config(conf)
    assert (cfg.n_layers, cfg.d_model, cfg.vocab) == (
        conf["num_hidden_layers"], conf["hidden_size"], conf["vocab_size"])


@pytest.mark.parametrize("model_type", ["deepseek_v3", "../run", "_dense",
                                        "qwen2/../minicpm"])
def test_an_unknown_model_type_names_the_modules(model_type):
    with pytest.raises(ValueError) as e:
        spec.arch(model_type)
    assert repr(model_type) in str(e.value)
    assert "['minicpm', 'qwen2']" in str(e.value)


def _root(tmp_path, **files):
    (tmp_path / "bench" / "kernels").mkdir(parents=True)
    shutil.copy(spec.BENCH / "kernel_names.json", tmp_path / "bench")
    for name, patterns in files.items():
        (tmp_path / "bench" / "kernels" / f"{name}.json").write_text(
            json.dumps({"about": "test", "patterns": patterns}))
    return tmp_path


def test_kernel_families_of_the_checkout():
    assert spec.kernel_families() == \
        spec.read_json("kernel_names.json")["families"]


def test_kernel_families_merge_added_files(tmp_path):
    root = _root(tmp_path, moe=["ragged_dot", "moe_[a-z]+_pallas"],
                 mla=["mla_decode"])
    base = spec.read_json("kernel_names.json")["families"]
    got = spec.kernel_families(root)
    assert list(got) == list(base) + ["mla", "moe"]
    assert got["moe"] == ["ragged_dot", "moe_[a-z]+_pallas"]
    assert all(got[f] == base[f] for f in base)


@pytest.mark.parametrize("files,error", [
    ({"gemm": ["x"]}, "named twice"),
    ({"bad": "int8"}, "list of strings"),
    ({"bad": ["("]}, "missing"),
])
def test_a_bad_family_file_is_an_error(tmp_path, files, error):
    with pytest.raises(Exception, match=error):
        spec.kernel_families(_root(tmp_path, **files))
