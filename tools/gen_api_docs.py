"""Generate the per-module API reference (docs/api/*.md) from docstrings.

ref counterpart: docs/source/*.rst + sphinx (the reference builds HTML on
readthedocs).  Here the reference pages are plain markdown generated
straight from the package's docstrings — run this after changing public
surfaces:

    JAX_PLATFORMS=cpu python tools/gen_api_docs.py

Pages: one per module listed in MODULES, each with the module docstring
and every public function/class (signature + full docstring).
"""
import importlib
import inspect
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

MODULES = [
    "apex_tpu.amp",
    "apex_tpu.amp.scaler",
    "apex_tpu.amp.functional",
    "apex_tpu.amp.lists",
    "apex_tpu.optimizers.fused_adam",
    "apex_tpu.optimizers.fused_lamb",
    "apex_tpu.optimizers.fused_sgd",
    "apex_tpu.optimizers.fused_novograd",
    "apex_tpu.optimizers.fused_adagrad",
    "apex_tpu.optimizers.larc",
    "apex_tpu.multi_tensor",
    "apex_tpu.bf16_utils",
    "apex_tpu.normalization",
    "apex_tpu.reparameterization",
    "apex_tpu.RNN.backend",
    "apex_tpu.mlp.mlp",
    "apex_tpu.ops.attention",
    "apex_tpu.ops.layer_norm",
    "apex_tpu.ops.softmax_xentropy",
    "apex_tpu.ops.mlp",
    "apex_tpu.ops.conv_bn",
    "apex_tpu.ops.gated_conv",
    "apex_tpu.ops.ssd",
    "apex_tpu.ops.kda",
    "apex_tpu.ops.fused_optim",
    "apex_tpu.parallel.distributed",
    "apex_tpu.parallel.sync_batchnorm",
    "apex_tpu.parallel.ring_attention",
    "apex_tpu.parallel.ulysses",
    "apex_tpu.parallel.tensor_parallel",
    "apex_tpu.parallel.moe",
    "apex_tpu.parallel.pipeline",
    "apex_tpu.parallel.mesh",
    "apex_tpu.parallel.multiproc",
    "apex_tpu.contrib.optimizers.distributed_fused",
    "apex_tpu.contrib.multihead_attn",
    "apex_tpu.contrib.groupbn",
    "apex_tpu.contrib.xentropy",
    "apex_tpu.contrib.sparsity",
    "apex_tpu.train.driver",
    "apex_tpu.train.accum",
    "apex_tpu.train.compress",
    "apex_tpu.sharding.rules",
    "apex_tpu.sharding.apply",
    "apex_tpu.remat",
    "apex_tpu.checkpoint",
    "apex_tpu.chip",
    "apex_tpu.data",
    "apex_tpu.pyprof.parse",
    "apex_tpu.pyprof.prof",
    "apex_tpu.models.resnet",
    "apex_tpu.models.bert",
    "apex_tpu.models.gpt",
    "apex_tpu.models.dcgan",
    "apex_tpu.models.decoder",
    "apex_tpu.models.lfm2",
    "apex_tpu.models.granite_hybrid",
    "apex_tpu.models.kimi_linear",
    "apex_tpu.serve.kv_cache",
    "apex_tpu.serve.decode",
    "apex_tpu.serve.engine",
    "apex_tpu.serve.handoff",
    "apex_tpu.serve.sharding",
    "apex_tpu.serve.loadgen",
    "apex_tpu.deploy.watch",
    "apex_tpu.deploy.reshard",
    "apex_tpu.deploy.promote",
    "apex_tpu.analysis.precision",
    "apex_tpu.analysis.donation",
    "apex_tpu.analysis.collectives",
    "apex_tpu.analysis.recompile",
    "apex_tpu.analysis.costs",
    "apex_tpu.analysis.staticcheck",
    "apex_tpu.analysis.dataflow",
    "apex_tpu.envs",
    "apex_tpu.obs.metrics",
    "apex_tpu.obs.trace",
    "apex_tpu.obs.lifecycle",
    "apex_tpu.obs.export",
    "apex_tpu.obs.slo",
    "apex_tpu.obs.flightrec",
    "apex_tpu.obs.gangview",
    "apex_tpu.obs.aggregate",
    "apex_tpu.resilience.faults",
    "apex_tpu.resilience.train",
    "apex_tpu.resilience.serve",
    "apex_tpu.fleet.serve",
    "apex_tpu.fleet.preflight",
    "apex_tpu.fleet.train",
]


def _public_members(mod):
    names = getattr(mod, "__all__", None)
    trust_all = names is not None  # __all__ IS the public surface,
    # including re-exports from implementation submodules
    if names is None:
        names = [n for n in vars(mod) if not n.startswith("_")]
    out = []
    for n in names:
        obj = getattr(mod, n, None)
        if obj is None:
            continue
        if inspect.isfunction(obj) or inspect.isclass(obj):
            # package pages without __all__ still list members defined in
            # their own submodules (re-exports), just not foreign imports
            if trust_all or getattr(obj, "__module__", "").startswith(
                mod.__name__
            ):
                out.append((n, obj))
    return out


def _sig(obj):
    try:
        s = str(inspect.signature(obj))
    except (TypeError, ValueError):
        return "(...)"
    # default-value reprs can embed memory addresses (flax module
    # sentinels, function defaults) — strip them so regeneration is
    # deterministic
    import re

    return re.sub(r"(?: object)? at 0x[0-9a-f]+", "", s)


def _doc(obj):
    import re

    d = inspect.getdoc(obj)
    if not d:
        return "(no docstring)"
    # flax auto-generated class docstrings embed default reprs with
    # memory addresses — strip for deterministic regeneration
    return re.sub(r"(?: object)? at 0x[0-9a-f]+", "", d.strip())


def render(modname):
    mod = importlib.import_module(modname)
    lines = [f"# `{modname}`", ""]
    if mod.__doc__:
        lines += [inspect.cleandoc(mod.__doc__), ""]
    for name, obj in _public_members(mod):
        kind = "class" if inspect.isclass(obj) else "def"
        lines += [f"## `{kind} {name}{_sig(obj)}`", "", _doc(obj), ""]
        if inspect.isclass(obj):
            for mname, raw in sorted(vars(obj).items()):
                if mname.startswith("_"):
                    continue
                if isinstance(raw, (staticmethod, classmethod)):
                    meth = raw.__func__
                elif isinstance(raw, property):
                    doc = inspect.getdoc(raw) or "(no docstring)"
                    lines += [f"### `{name}.{mname}` (property)", "",
                              doc.strip(), ""]
                    continue
                elif inspect.isfunction(raw):
                    meth = raw
                else:
                    continue
                lines += [f"### `{name}.{mname}{_sig(meth)}`", "",
                          _doc(meth), ""]
    return "\n".join(lines) + "\n"


def main():
    outdir = os.path.join(os.path.dirname(__file__), "..", "docs", "api")
    os.makedirs(outdir, exist_ok=True)
    index = ["# apex_tpu API reference",
             "",
             "Generated from docstrings by `tools/gen_api_docs.py` — the",
             "per-module counterpart of the reference's sphinx pages",
             "(ref docs/source/*.rst).  Docstrings cite the reference",
             "files they implement (file:line) for the parity crosswalk.",
             ""]
    for modname in MODULES:
        fname = modname.replace(".", "_") + ".md"
        with open(os.path.join(outdir, fname), "w") as f:
            f.write(render(modname))
        index.append(f"- [{modname}]({fname})")
    with open(os.path.join(outdir, "index.md"), "w") as f:
        f.write("\n".join(index) + "\n")
    print(f"wrote {len(MODULES)} module pages + index to {outdir}")


if __name__ == "__main__":
    main()
