import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST stay first — jax locks the device count on first
backend init, and only the dry-run wants 512 placeholder devices.

Per cell this:
  1. builds the full-size config and abstract (ShapeDtypeStruct) params /
     optimizer / decode-state trees — no allocation anywhere;
  2. jits the step (train_step / prefill_step / serve_step) with the
     sharding rules from repro.launch.sharding, lowers against
     input_specs(), compiles, and prints memory_analysis + cost_analysis;
  3. compiles the scan-unit body standalone and composes exact totals
     (module + (R-1) × body — XLA counts while bodies once, trip counts are
     known statically here);
  4. parses per-device collective bytes out of the HLO for the roofline's
     third term, and writes everything to results/dryrun/<cell>.json.

Usage:
  python -m repro.launch.dryrun --arch yi_6b --shape train_4k --mesh multi
  python -m repro.launch.dryrun --all [--mesh both] [--out results/dryrun]
"""
import argparse
import functools
import json
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, arch_ids, get_config, shape_applicable
from repro.launch.hlo_analysis import collective_bytes, collective_count
from repro.launch.mesh import make_production_mesh
from repro.launch.sharding import (
    batch_pspec, opt_state_pspecs, state_pspecs, tree_pspecs,
)
from repro.launch.specs import input_specs
from repro.models.stacked import (
    _unit_apply, forward_scan, group_split, init_decode_state_stacked,
    init_params_stacked, lm_loss_scan, decode_step_scan, unit_kinds,
)
from repro.models.transformer import MESH_AXES_MULTI, MESH_AXES_SINGLE
from repro.train.optim import make_optimizer

ADAFACTOR_THRESHOLD = 100e9  # params above this use factored moments


def _mesh_axes(multi_pod: bool):
    return MESH_AXES_MULTI if multi_pod else MESH_AXES_SINGLE


def _param_count(tree) -> int:
    return sum(int(x.size if hasattr(x, "size") else 0)
               for x in jax.tree_util.tree_leaves(tree))


def _sh(mesh, spec):
    return NamedSharding(mesh, spec)


def _tree_sh(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda s: isinstance(s, P))


def _analyze(lowered, compiled) -> Dict[str, Any]:
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    text = compiled.as_text()
    coll_total, coll_kinds = collective_bytes(text)
    return {
        "memory": {
            "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
            "alias_bytes": int(getattr(ma, "alias_size_in_bytes", 0)),
        },
        "cost": {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        },
        "collectives": {"bytes": int(coll_total), "by_kind": coll_kinds,
                        "count": collective_count(text)},
    }


def _body_cost(cfg, mesh, mesh_axes, shape, kind: str, abs_params,
               abs_state=None, fsdp: bool = False):
    """Compile one scan unit standalone → per-iteration cost/collectives."""
    u_kinds = unit_kinds(cfg)
    b = shape["global_batch"]
    s = shape["seq_len"] if kind != "decode" else 1
    act_dt = jnp.dtype(cfg.dtype)

    abs_unit = [jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), g)
        for g in abs_params["scan"]]
    unit_specs = [tree_pspecs(u, mesh, fsdp=fsdp) for u in abs_unit]

    x_sds = jax.ShapeDtypeStruct((b, s, cfg.d_model), act_dt)
    # Activations are replicated across the model axis between blocks
    # (§Perf iteration 2) — the body probe must match or it measures
    # spurious boundary re-sharding.
    x_spec = P(batch_pspec((b, s, cfg.d_model), mesh)[0], None, None)
    if cfg.mrope_sections is not None:
        pos_sds = jax.ShapeDtypeStruct((3, b, shape["seq_len"]), jnp.int32)
        pos_spec = P(None, batch_pspec((b,), mesh)[0], None)
    else:
        pos_sds = jax.ShapeDtypeStruct((b, s), jnp.int32)
        pos_spec = batch_pspec((b, s), mesh)

    enc_args = ()
    enc_in_sh = ()
    if cfg.is_enc_dec and kind != "decode":
        enc_sds = jax.ShapeDtypeStruct((b, cfg.audio_frames, cfg.d_model), act_dt)
        enc_spec = batch_pspec((b, cfg.audio_frames, cfg.d_model), mesh)
        enc_args = (enc_sds,)
        enc_in_sh = (_sh(mesh, enc_spec),)

    if kind == "train":
        def body(x, ct, positions, *rest):
            enc_out = rest[-1] if cfg.is_enc_dec else None
            unit = rest[: len(abs_unit)]
            f = lambda x_, unit_: _unit_apply(
                cfg, u_kinds, unit_, x_, positions, mesh_axes, enc_out)[0]
            y, pull = jax.vjp(f, x, tuple(unit))
            dx, dunit = pull(ct)
            return y, dx, dunit

        args = (x_sds, x_sds, pos_sds, *abs_unit, *enc_args)
        in_sh = (_sh(mesh, x_spec), _sh(mesh, x_spec), _sh(mesh, pos_spec),
                 *[_tree_sh(mesh, sp) for sp in unit_specs], *enc_in_sh)
    elif kind == "prefill":
        def body(x, positions, *rest):
            enc_out = rest[-1] if cfg.is_enc_dec else None
            unit = rest[: len(abs_unit)]
            return _unit_apply(cfg, u_kinds, tuple(unit), x, positions,
                               mesh_axes, enc_out)[0]

        args = (x_sds, pos_sds, *abs_unit, *enc_args)
        in_sh = (_sh(mesh, x_spec), _sh(mesh, pos_spec),
                 *[_tree_sh(mesh, sp) for sp in unit_specs], *enc_in_sh)
    else:  # decode: one unit step against stacked-state slice
        abs_unit_state = [jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), g)
            for g in abs_state["scan"]]
        state_specs = [state_pspecs(st, mesh) for st in abs_unit_state]

        def body(x, pos, *rest):
            unit = rest[: len(abs_unit)]
            states = rest[len(abs_unit):]
            from repro.models.stacked import decode_step_scan  # noqa
            # apply one unit (same code path as the scan body)
            from repro.models.stacked import BlockKind  # noqa
            x_ = x
            new_states = []
            # reuse the scan body's per-layer application
            for j, k_ in enumerate(u_kinds):
                x_, ns = _decode_apply_one(cfg, k_, unit[j], states[j], x_,
                                           pos)
                new_states.append(ns)
            return x_, tuple(new_states)

        x1 = jax.ShapeDtypeStruct((b, 1, cfg.d_model), act_dt)
        x1_spec = P(batch_pspec((b,), mesh)[0], None, None)
        pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
        args = (x1, pos_sds, *abs_unit, *abs_unit_state)
        in_sh = (_sh(mesh, x1_spec), _sh(mesh, P()),
                 *[_tree_sh(mesh, sp) for sp in unit_specs],
                 *[_tree_sh(mesh, sp) for sp in state_specs])

    with jax.set_mesh(mesh):
        lowered = jax.jit(body, in_shardings=in_sh).lower(*args)
        compiled = lowered.compile()
    return _analyze(lowered, compiled)


def _decode_apply_one(cfg, kind, p, st, x, pos):
    """Single-layer decode application shared with decode_step_scan."""
    from repro.models.stacked import decode_step_scan  # circular-safe
    from repro.models import layers as L
    from repro.models import recurrent as R_
    from repro.models.config import BlockKind
    from repro.models.transformer import _decode_attn

    h = L.rms_norm(x, p["ln1"])
    if kind in (BlockKind.ATTN, BlockKind.MOE, BlockKind.LOCAL_ATTN):
        window = cfg.sliding_window if kind == BlockKind.LOCAL_ATTN else None
        attn_out, new_st = _decode_attn(cfg, p["attn"], h, st, pos, window,
                                        ring=kind == BlockKind.LOCAL_ATTN)
        x = x + attn_out
        h2 = L.rms_norm(x, p["ln2"])
        if kind == BlockKind.MOE:
            ffn_out, _ = L.moe_ffn(cfg, p["moe"], h2)
        elif "mlp" in p:
            ffn_out = L.mlp(p["mlp"], h2)
        else:
            ffn_out = jnp.zeros_like(x)
        x = x + ffn_out
    elif kind == BlockKind.MLSTM:
        y, new_st = R_.mlstm_step(p["mlstm"], h, st, cfg.n_heads)
        x = x + y
        if "mlp" in p:
            x = x + L.mlp(p["mlp"], L.rms_norm(x, p["ln2"]))
    elif kind == BlockKind.SLSTM:
        y, new_st = R_.slstm_step(p["slstm"], h, st)
        x = x + y
        if "mlp" in p:
            x = x + L.mlp(p["mlp"], L.rms_norm(x, p["ln2"]))
    else:  # RGLRU
        rp = p["rec"]
        gate = jax.nn.gelu(h @ rp["w_branch_gate"])
        lin = h @ rp["w_branch_lin"]
        lin, conv_st = R_.temporal_conv_step(rp, lin, st["conv"], cfg.conv_width)
        rec, h_st = R_.rglru_step(rp, lin, st["h"])
        new_st = {"h": h_st, "conv": conv_st}
        x = x + (gate * rec) @ rp["w_out"]
        if "mlp" in p:
            x = x + L.mlp(p["mlp"], L.rms_norm(x, p["ln2"]))
    return x, new_st


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             body_costs: bool = True) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_axes = _mesh_axes(multi_pod)
    kind = shape["kind"]
    result: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": kind, "ok": False,
    }

    runs, reason = shape_applicable(arch, shape_name)
    if not runs:
        result["skipped"] = reason
        return result

    t0 = time.time()
    abs_params = jax.eval_shape(
        functools.partial(init_params_stacked, cfg), jax.random.PRNGKey(0))
    n_params = _param_count(abs_params)
    result["params"] = n_params
    # FSDP only when bf16 params can't replicate across the data axis
    # (per-device share with TP-16 would blow HBM); smaller models keep
    # params TP-only + ZeRO-1 optimizer sharding — far fewer collectives.
    fsdp = n_params > 30e9
    result["fsdp"] = fsdp
    param_specs = tree_pspecs(abs_params, mesh, fsdp=fsdp)
    specs = input_specs(cfg, shape)
    r, rem = group_split(cfg)
    result["scan_repeats"] = r

    if kind == "train":
        opt_name = "adafactor" if n_params > ADAFACTOR_THRESHOLD else "adamw"
        result["optimizer"] = opt_name
        opt_init, opt_update = make_optimizer(opt_name, lr=1e-4)
        abs_opt = jax.eval_shape(opt_init, abs_params)
        opt_specs = opt_state_pspecs(abs_opt, param_specs, mesh)

        def train_step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(
                lambda p: lm_loss_scan(
                    cfg, p, batch["tokens"], batch["labels"],
                    vision_embeds=batch.get("vision_embeds"),
                    audio_embeds=batch.get("audio_embeds"),
                    mesh_axes=mesh_axes))(params)
            params, opt_state = opt_update(params, grads, opt_state)
            return loss, params, opt_state

        batch_specs = {k: batch_pspec(v.shape, mesh) for k, v in specs.items()}
        in_sh = (_tree_sh(mesh, param_specs), _tree_sh(mesh, opt_specs),
                 {k: _sh(mesh, s) for k, s in batch_specs.items()})
        out_sh = (_sh(mesh, P()), _tree_sh(mesh, param_specs),
                  _tree_sh(mesh, opt_specs))
        step = jax.jit(train_step, in_shardings=in_sh, out_shardings=out_sh)
        args = (abs_params, abs_opt, specs)

    elif kind == "prefill":
        def prefill_step(params, batch):
            logits, _ = forward_scan(
                cfg, params, batch["tokens"],
                vision_embeds=batch.get("vision_embeds"),
                audio_embeds=batch.get("audio_embeds"),
                mesh_axes=mesh_axes, last_only=True)
            return logits

        batch_specs = {k: batch_pspec(v.shape, mesh) for k, v in specs.items()}
        in_sh = (_tree_sh(mesh, param_specs),
                 {k: _sh(mesh, s) for k, s in batch_specs.items()})
        step = jax.jit(prefill_step, in_shardings=in_sh)
        args = (abs_params, specs)

    else:  # decode
        abs_state = jax.eval_shape(
            functools.partial(init_decode_state_stacked, cfg,
                              shape["global_batch"], shape["seq_len"]))
        st_specs = state_pspecs(abs_state, mesh)

        def serve_step(params, token, state, enc_out=None):
            return decode_step_scan(cfg, params, token, state,
                                    enc_out=enc_out, mesh_axes=mesh_axes)

        tok_spec = batch_pspec(specs["token"].shape, mesh)
        in_sh = [_tree_sh(mesh, param_specs), _sh(mesh, tok_spec),
                 _tree_sh(mesh, st_specs)]
        args = [abs_params, specs["token"], abs_state]
        if cfg.is_enc_dec:
            in_sh.append(_sh(mesh, batch_pspec(specs["enc_out"].shape, mesh)))
            args.append(specs["enc_out"])
        step = jax.jit(serve_step, in_shardings=tuple(in_sh))
        args = tuple(args)

    try:
        with jax.set_mesh(mesh):
            t_l = time.time()
            lowered = step.lower(*args)
            result["lower_s"] = round(time.time() - t_l, 2)
            t_c = time.time()
            compiled = lowered.compile()
            result["compile_s"] = round(time.time() - t_c, 2)
            print(compiled.memory_analysis())   # proves it fits
            print(compiled.cost_analysis())     # FLOPs/bytes for §Roofline
        result.update(_analyze(lowered, compiled))

        if body_costs:
            abs_state = (jax.eval_shape(
                functools.partial(init_decode_state_stacked, cfg,
                                  shape["global_batch"], shape["seq_len"]))
                if kind == "decode" else None)
            body = _body_cost(cfg, mesh, mesh_axes, shape, kind,
                              abs_params, abs_state, fsdp=fsdp)
            result["body"] = body
            # exact totals: module counts each scan body once
            mult = max(r - 1, 0)
            result["total_flops"] = (result["cost"]["flops"]
                                     + mult * body["cost"]["flops"])
            result["total_bytes_accessed"] = (
                result["cost"]["bytes_accessed"]
                + mult * body["cost"]["bytes_accessed"])
            result["total_collective_bytes"] = (
                result["collectives"]["bytes"]
                + mult * body["collectives"]["bytes"])
        result["ok"] = True
    except Exception as err:  # noqa: BLE001
        result["error"] = f"{type(err).__name__}: {err}"
        result["traceback"] = traceback.format_exc()[-2000:]
    result["elapsed_s"] = round(time.time() - t0, 2)
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--no-body", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    cells = []
    archs = arch_ids() if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                cells.append((arch, shape, mp))

    for arch, shape, mp in cells:
        name = f"{arch}__{shape}__{'multi' if mp else 'single'}"
        path = os.path.join(args.out, name + ".json")
        if os.path.exists(path) and not args.force:
            print(f"[skip] {name} (exists)")
            continue
        print(f"[run ] {name}", flush=True)
        res = run_cell(arch, shape, mp, body_costs=not args.no_body)
        with open(path, "w") as f:
            json.dump(res, f, indent=1)
        status = ("OK" if res.get("ok")
                  else ("SKIP: " + res["skipped"]) if "skipped" in res
                  else "FAIL: " + res.get("error", "?"))
        print(f"[done] {name}: {status} ({res.get('elapsed_s', 0)}s)",
              flush=True)


if __name__ == "__main__":
    main()
