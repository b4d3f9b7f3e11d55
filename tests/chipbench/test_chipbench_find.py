"""`manifest.find`: every name a data file gives resolves to code, through one
resolver for the five kinds (drivers, models, reference, work, reducers); the
functions that came with the harness and a file of a later PR are found under
the same rule, and a name with no file raises a KeyError that names the file
it looked for."""
import pytest

from chipbench import manifest, reducers, work

MF = manifest.load_manifest()
SPECS = {m["name"]: manifest.load_json(
    manifest.HERE / "metrics" / f"{m['name']}.json") for m in MF["per_layer"]}


@pytest.mark.parametrize("name", sorted(SPECS))
def test_every_metric_files_reducer_and_work_function_are_found(name):
    spec = SPECS[name]
    fn = manifest.find("reducers", spec["reducer"])
    assert callable(fn) and fn.__name__ == spec["reducer"]
    if "work" in spec:
        fn = manifest.find("work", spec["work"])
        assert callable(fn) and fn.__name__ == spec["work"]


def test_the_names_in_use_are_the_names_the_resolver_can_find():
    assert {s["reducer"] for s in SPECS.values()} \
        == set(manifest.names("reducers")) == set(reducers.__all__)
    assert {s["work"] for s in SPECS.values() if "work" in s} \
        == set(manifest.names("work")) == set(work.__all__)
    assert manifest.find("reducers", "mfu") is reducers.mfu
    assert manifest.find("work", "decode_flops") is work.decode_flops
    # a helper of the kind's home is no member of the kind
    with pytest.raises(KeyError, match=r"chipbench/work/lm_matmul_params\.py"):
        manifest.find("work", "lm_matmul_params")
    assert manifest.names("drivers") == ["fedavg", "fedlora", "rounds",
                                         "serve"]
    assert manifest.names("models") == ["olmo"]


@pytest.mark.parametrize("cell", [w["name"] for w in MF["workloads"]])
def test_a_cells_driver_reference_and_model_builder_are_found(cell):
    c = manifest.Cell(MF, cell)
    assert manifest.find("drivers", c.driver).__name__ == "Driver"
    assert manifest.find("reference", c.config_name) is c.reference()
    kind = c.config["model"].get("model_type")
    if kind:
        build = manifest.find("models", kind)
        assert build.__module__ == f"chipbench.models.{kind}"


@pytest.mark.parametrize("kind,name,path", [
    ("reducers", "no_such_kind", "chipbench/reducers/no_such_kind.py"),
    ("work", "no_such_work", "chipbench/work/no_such_work.py"),
    ("drivers", "no_such_driver", "chipbench/drivers/no_such_driver.py"),
    ("models", "no_such_type", "chipbench/models/no_such_type.py"),
    ("reference", "no_such_config", "chipbench/reference/no_such_config.py")])
def test_a_name_with_no_file_raises_a_keyerror_that_names_the_file(
        kind, name, path):
    with pytest.raises(KeyError, match=path.replace(".", r"\.")):
        manifest.find(kind, name)


def test_an_unknown_kind_a_name_no_file_can_have_and_a_file_without_its_export():
    with pytest.raises(KeyError, match="no such kind of code"):
        manifest.find("kernels", "x")
    with pytest.raises(KeyError, match="no name a file can have"):
        manifest.find("work", "../run")
    # drivers/rounds.py is what two kinds share: no kind, it has no `Driver`
    with pytest.raises(KeyError, match="defines no 'Driver'"):
        manifest.find("drivers", "rounds")


def test_the_olmo_builder_refuses_what_the_programs_block_cannot_express():
    build = manifest.find("models", "olmo")
    model = manifest.Cell(MF, "olmo1b_decode_chat").config["model"]
    module, spec = build(model)
    assert spec == {"model_kind": "lm", "lm": {
        "vocab_size": 50304, "d_model": 2048, "n_layers": 16, "n_heads": 16,
        "d_ff": 8192, "scan_layers": True}}
    assert (module.d_model, module.n_layers, module.remat) == (2048, 16, False)
    assert build(model, remat=True)[0].remat is True
    with pytest.raises(ValueError, match="8 KV heads"):
        build({**model, "num_key_value_heads": 8})
    with pytest.raises(ValueError, match="KV heads of 64"):
        build({**model, "head_dim": 64})
