"""paddle.static.nn parity surface: control flow + static layer helpers.

Reference: python/paddle/static/nn/__init__.py (fc + control_flow ops from
fluid/layers/control_flow.py).
"""
from __future__ import annotations

from .control_flow import case, cond, switch_case, while_loop  # noqa: F401

__all__ = [
    "cond", "while_loop", "case", "switch_case", "fc", "conv2d", "conv3d",
    "conv2d_transpose", "conv3d_transpose", "batch_norm", "layer_norm",
    "group_norm", "instance_norm", "data_norm", "spectral_norm", "embedding",
    "sparse_embedding", "prelu", "bilinear_tensor_product", "row_conv",
    "crf_decoding", "nce", "multi_box_head", "deform_conv2d", "py_func",
    "sequence_concat", "sequence_conv", "sequence_enumerate",
    "sequence_expand", "sequence_expand_as", "sequence_first_step",
    "sequence_last_step", "sequence_pad", "sequence_pool",
    "sequence_reshape", "sequence_reverse", "sequence_scatter",
    "sequence_slice", "sequence_softmax", "sequence_unpad",
]

from ..nn.functional.sequence import (  # noqa: F401,E402
    sequence_concat, sequence_conv, sequence_enumerate, sequence_expand,
    sequence_expand_as, sequence_first_step, sequence_last_step,
    sequence_pad, sequence_pool, sequence_reshape, sequence_reverse,
    sequence_scatter, sequence_slice, sequence_softmax, sequence_unpad,
)


def fc(x, size, num_flatten_dims=1, weight_attr=None, bias_attr=None,
       activation=None, name=None):
    """Static fully-connected helper (reference static/nn/common.py fc):
    flattens trailing dims, applies xW+b and optional activation."""
    name = _uname("fc", name)
    import numpy as np

    from .. import tensor as T
    from ..framework.core import Parameter
    from ..nn import functional as F
    from ..nn import initializer as I

    shape = list(x.shape)
    in_features = int(np.prod(shape[num_flatten_dims:]))
    if num_flatten_dims != len(shape) - 1 or len(shape) > 2:
        x = T.reshape(x, shape[:num_flatten_dims] + [in_features])
    w = Parameter(I.XavierNormal()((in_features, size), "float32"),
                  name=name + ".w")
    out = T.matmul(x, w)
    if bias_attr is not False:
        b = Parameter(I.Constant(0.0)((size,), "float32"),
                      name=name + ".b")
        out = out + b
    if activation == "relu":
        out = F.relu(out)
    elif activation == "tanh":
        out = T.tanh(out)
    elif activation == "sigmoid":
        out = F.sigmoid(out)
    elif activation is not None:
        raise ValueError(f"unsupported activation {activation}")
    return out


# ---------------------------------------------------------------------------
# static layer wrappers (reference python/paddle/static/nn/__init__.py):
# each creates its Parameters inline (captured by the traced Program as
# leaves, static/graph.py) and applies the op — the LayerHelper pattern
# without a LayerHelper.
# ---------------------------------------------------------------------------

def _uname(base, name):
    """Auto-unique parameter-name prefix (the reference LayerHelper
    uniquifies every created var; fixed names would collide in
    static.save's name-keyed state dict). Counters live ON the active
    Program so rebuilding the same graph reproduces the same names and
    save/rebuild/load round-trips."""
    if name is not None:
        return name
    from . import default_main_program

    prog = default_main_program()
    counters = getattr(prog, "_uname_counters", None)
    if counters is None:
        counters = prog._uname_counters = {}
    n = counters.get(base, 0)
    counters[base] = n + 1
    return "%s_%d" % (base, n)


def conv2d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,  # noqa: A002
           groups=1, param_attr=None, bias_attr=None, use_cudnn=True,
           act=None, name=None, data_format="NCHW"):
    from ..nn import Conv2D

    out = Conv2D(int(input.shape[1]), num_filters, filter_size,
                 stride=stride, padding=padding, dilation=dilation,
                 groups=groups, weight_attr=param_attr, bias_attr=bias_attr,
                 data_format=data_format)(input)
    return _act(out, act)


def conv3d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,  # noqa: A002
           groups=1, param_attr=None, bias_attr=None, use_cudnn=True,
           act=None, name=None, data_format="NCDHW"):
    from ..nn import Conv3D

    out = Conv3D(int(input.shape[1]), num_filters, filter_size,
                 stride=stride, padding=padding, dilation=dilation,
                 groups=groups, weight_attr=param_attr, bias_attr=bias_attr,
                 data_format=data_format)(input)
    return _act(out, act)



def _deconv_filter(filter_size, output_size, in_spatial, stride, padding):
    """Reference conv2d_transpose: filter_size derived from output_size
    when omitted (k = out - (in-1)*stride + 2*pad)."""
    if filter_size is not None:
        return filter_size
    if output_size is None:
        raise ValueError(
            "conv transpose needs filter_size or output_size")
    outs = ([int(output_size)] * len(in_spatial)
            if isinstance(output_size, int) else [int(v) for v in output_size])
    st = ([int(stride)] * len(in_spatial) if isinstance(stride, int)
          else [int(v) for v in stride])
    pd = ([int(padding)] * len(in_spatial) if isinstance(padding, int)
          else [int(v) for v in padding])
    return [outs[i] - (int(in_spatial[i]) - 1) * st[i] + 2 * pd[i]
            for i in range(len(in_spatial))]

def conv2d_transpose(input, num_filters, output_size=None, filter_size=None,  # noqa: A002
                     padding=0, stride=1, dilation=1, groups=1,
                     param_attr=None, bias_attr=None, use_cudnn=True,
                     act=None, name=None, data_format="NCHW"):
    from ..nn import Conv2DTranspose

    filter_size = _deconv_filter(filter_size, output_size, input.shape[2:],
                                 stride, padding)
    out = Conv2DTranspose(int(input.shape[1]), num_filters, filter_size,
                          stride=stride, padding=padding, dilation=dilation,
                          groups=groups, weight_attr=param_attr,
                          bias_attr=bias_attr, data_format=data_format)(input)
    return _act(out, act)


def conv3d_transpose(input, num_filters, output_size=None, filter_size=None,  # noqa: A002
                     padding=0, stride=1, dilation=1, groups=1,
                     param_attr=None, bias_attr=None, use_cudnn=True,
                     act=None, name=None, data_format="NCDHW"):
    from ..nn import Conv3DTranspose

    filter_size = _deconv_filter(filter_size, output_size, input.shape[2:],
                                 stride, padding)
    out = Conv3DTranspose(int(input.shape[1]), num_filters, filter_size,
                          stride=stride, padding=padding, dilation=dilation,
                          groups=groups, weight_attr=param_attr,
                          bias_attr=bias_attr, data_format=data_format)(input)
    return _act(out, act)


def _act(out, act):
    if act is None:
        return out
    from ..nn import functional as F

    fn = getattr(F, act, None)
    if fn is None:
        raise ValueError("unsupported activation %r" % (act,))
    return fn(out)


def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-5,  # noqa: A002
               param_attr=None, bias_attr=None, data_layout="NCHW",
               name=None, moving_mean_name=None, moving_variance_name=None,
               do_model_average_for_mean_and_var=True, use_global_stats=False):
    from ..nn import BatchNorm

    bn = BatchNorm(int(input.shape[1]), momentum=momentum, epsilon=epsilon,
                   param_attr=param_attr, bias_attr=bias_attr,
                   data_layout=data_layout, use_global_stats=use_global_stats)
    if is_test:
        bn.eval()
    return _act(bn(input), act)


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1,  # noqa: A002
               epsilon=1e-5, param_attr=None, bias_attr=None, act=None,
               name=None):
    from ..nn import LayerNorm

    shape = [int(s) for s in input.shape[begin_norm_axis:]]
    ln = LayerNorm(shape, epsilon=epsilon,
                   weight_attr=param_attr if scale else False,
                   bias_attr=bias_attr if shift else False)
    return _act(ln(input), act)


def group_norm(input, groups, epsilon=1e-5, param_attr=None, bias_attr=None,  # noqa: A002
               act=None, data_layout="NCHW", name=None):
    from ..nn import GroupNorm

    gn = GroupNorm(groups, int(input.shape[1]), epsilon=epsilon,
                   weight_attr=param_attr, bias_attr=bias_attr)
    return _act(gn(input), act)


def instance_norm(input, epsilon=1e-5, param_attr=None, bias_attr=None,  # noqa: A002
                  name=None):
    from ..nn import InstanceNorm2D

    return InstanceNorm2D(int(input.shape[1]), epsilon=epsilon,
                          weight_attr=param_attr, bias_attr=bias_attr)(input)


def data_norm(input, act=None, epsilon=1e-5, param_attr=None,  # noqa: A002
              data_layout="NCHW", in_place=False, name=None,
              moving_mean_name=None, moving_variance_name=None,
              do_model_average_for_mean_and_var=True, slot_dim=-1,
              sync_stats=False, summary_decay_rate=0.9999999, enable_scale_and_shift=False):
    """Reference data_norm_op.cc: normalization by accumulated batch
    statistics (batch_size/batch_sum/batch_square_sum), no learned gamma:
    out = (x - sum/size) / sqrt(square_sum/size - mean^2 + eps)."""
    name = _uname("dn", name)
    import jax.numpy as jnp

    from ..framework.core import Parameter, apply_op
    from ..nn import initializer as I

    D = int(input.shape[1])
    # accumulated statistics, NOT gradient-trained (reference data_norm_op
    # updates them by in-place accumulation, not SGD)
    size = Parameter(I.Constant(1e4)((D,), "float32"),
                     name=name + ".size", trainable=False)
    sums = Parameter(I.Constant(0.0)((D,), "float32"),
                     name=name + ".sum", trainable=False)
    sqs = Parameter(I.Constant(1e4)((D,), "float32"),
                    name=name + ".sq", trainable=False)

    def _dn(x, size, sums, sqs, epsilon):
        mean = sums / size
        var = sqs / size - mean * mean
        return (x - mean) / jnp.sqrt(var + epsilon)

    return _act(apply_op(_dn, input, size, sums, sqs,
                         epsilon=float(epsilon), op_name="data_norm"), act)


def spectral_norm(weight, dim=0, power_iters=1, eps=1e-12, name=None):
    """Power-iteration spectral normalization of a weight tensor
    (reference spectral_norm_op.cc), returning weight / sigma."""
    import jax.numpy as jnp

    from ..framework.core import apply_op

    def _sn(w, dim, power_iters, eps):
        wm = jnp.moveaxis(w, dim, 0).reshape(w.shape[dim], -1)
        u = jnp.ones((wm.shape[0],), w.dtype) / jnp.sqrt(wm.shape[0])
        v = jnp.ones((wm.shape[1],), w.dtype) / jnp.sqrt(wm.shape[1])
        for _ in range(max(power_iters, 1)):
            v = wm.T @ u
            v = v / (jnp.linalg.norm(v) + eps)
            u = wm @ v
            u = u / (jnp.linalg.norm(u) + eps)
        sigma = u @ wm @ v
        return w / sigma

    return apply_op(_sn, weight, dim=int(dim), power_iters=int(power_iters),
                    eps=float(eps), op_name="spectral_norm")


def embedding(input, size, is_sparse=False, is_distributed=False,  # noqa: A002
              padding_idx=None, param_attr=None, dtype="float32"):
    from ..nn import Embedding

    emb = Embedding(int(size[0]), int(size[1]), padding_idx=padding_idx,
                    sparse=is_sparse, weight_attr=param_attr)
    return emb(input)


def sparse_embedding(input, size, padding_idx=None, is_test=False,  # noqa: A002
                     entry=None, param_attr=None, dtype="float32"):
    """Reference sparse_embedding: PS-backed huge embedding table. Per the
    parameter-server decision (README), the table is dense here; ``entry``
    admission configs are accepted and ignored."""
    return embedding(input, size, is_sparse=True, padding_idx=padding_idx,
                     param_attr=param_attr, dtype=dtype)


def prelu(x, mode="all", param_attr=None, name=None):
    from ..framework.core import Parameter
    from ..nn import functional as F
    from ..nn import initializer as I

    if mode == "all":
        shape = (1,)
    elif mode == "channel":
        shape = (int(x.shape[1]),)
    elif mode == "element":
        shape = tuple(int(s) for s in x.shape[1:])
    else:
        raise ValueError("mode must be all/channel/element")
    alpha = Parameter(I.Constant(0.25)(shape, "float32"),
                      name=_uname("prelu", name) + ".alpha")
    return F.prelu(x, alpha)


def bilinear_tensor_product(x, y, size, act=None, name=None,
                            param_attr=None, bias_attr=None):
    """out_k = x W_k y^T + b (reference bilinear_tensor_product_op.cc)."""
    name = _uname("btp", name)
    import jax.numpy as jnp

    from ..framework.core import Parameter, apply_op
    from ..nn import initializer as I

    dx, dy = int(x.shape[-1]), int(y.shape[-1])
    w = Parameter(I.XavierNormal()((size, dx, dy), "float32"),
                  name=name + ".w")
    b = Parameter(I.Constant(0.0)((size,), "float32"),
                  name=name + ".b")

    def _btp(x, y, w, b):
        return jnp.einsum("bd,kde,be->bk", x, w, y) + b

    return _act(apply_op(_btp, x, y, w, b, op_name="bilinear_tensor_product"),
                act)


def row_conv(input, future_context_size, param_attr=None, act=None):  # noqa: A002
    """Lookahead row convolution (reference row_conv_op.cc):
    out[t] = sum_{i=0..ctx} w[i] * x[t+i], per feature."""
    import jax.numpy as jnp

    from ..framework.core import Parameter, apply_op
    from ..nn import initializer as I

    D = int(input.shape[-1])
    ctx = int(future_context_size) + 1
    w = Parameter(I.XavierNormal()((ctx, D), "float32"), name=_uname("row_conv", None) + ".w")

    def _rc(x, w):
        T = x.shape[1]
        out = jnp.zeros_like(x)
        for i in range(w.shape[0]):
            shifted = jnp.roll(x, -i, axis=1)
            ok = (jnp.arange(T) + i < T)[None, :, None]
            out = out + jnp.where(ok, shifted, 0.0) * w[i]
        return out

    return _act(apply_op(_rc, input, w, op_name="row_conv"), act)


def crf_decoding(input, param_attr, label=None, length=None):  # noqa: A002
    """Viterbi decode with learned CRF transitions (reference
    crf_decoding_op.h). ``param_attr`` here IS the transition tensor
    ([num_tags + 2, num_tags]: rows 0/1 are start/stop, like
    linear_chain_crf_op) — the reference resolved it by parameter name
    through the Scope, which the traced program replaces with direct
    capture."""
    import jax.numpy as jnp

    from ..framework.core import Tensor
    from ..text import viterbi_decode

    trans = param_attr
    ta = trans._data if isinstance(trans, Tensor) else jnp.asarray(trans)
    # linear_chain_crf layout [num_tags+2, num_tags]: row 0 = start scores,
    # row 1 = stop scores, rows 2.. = pairwise. Fold start/stop into the
    # emissions, decode with the pairwise matrix.
    emis = input._data if isinstance(input, Tensor) else jnp.asarray(input)
    B, T_len, _ = emis.shape
    if length is not None:
        lens = (length._data if isinstance(length, Tensor)
                else jnp.asarray(length)).reshape(-1)
    else:
        lens = jnp.full((B,), T_len, jnp.int32)
    emis = emis.at[:, 0].add(ta[0])
    last = jnp.maximum(lens - 1, 0).astype(jnp.int32)
    emis = emis.at[jnp.arange(B), last].add(ta[1])
    scores, path = viterbi_decode(Tensor(emis), Tensor(ta[2:]), Tensor(lens),
                                  include_bos_eos_tag=False)
    if label is not None:
        from ..framework.core import apply_op

        return apply_op(lambda p, l: (p == l.reshape(p.shape)).astype("int64"),
                        path, label, op_name="crf_decoding_check")
    return path


def nce(input, label, num_total_classes, sample_weight=None,  # noqa: A002
        param_attr=None, bias_attr=None, num_neg_samples=10, name=None,
        sampler="uniform", custom_dist=None, seed=0, is_sparse=False):
    """Noise-contrastive estimation loss (reference nce_op.h): binary
    logistic on the true class vs num_neg_samples uniform negatives."""
    name = _uname("nce", name)
    import jax
    import jax.numpy as jnp

    from ..framework.core import Parameter, apply_op
    from ..framework.random import next_key
    from ..nn import initializer as I

    if sampler != "uniform" or custom_dist is not None:
        raise NotImplementedError(
            "nce: only the uniform sampler is implemented")
    D = int(input.shape[-1])
    w = Parameter(I.XavierNormal()((num_total_classes, D), "float32"),
                  name=name + ".w")
    b = Parameter(I.Constant(0.0)((num_total_classes,), "float32"),
                  name=name + ".b")
    # Eager mode: negatives refresh per call — seed=0 draws from the
    # advancing global PRNG; an explicit seed gets a deterministic but
    # still advancing stream (fold_in of a call counter), matching the
    # reference sampler. Static mode captures the build-time key, the same
    # frozen-randomness semantics as every random op in a traced Program
    # (see nn/functional/common.py dropout).
    if seed:
        # per-Program call index (like _uname): rebuilding the same graph
        # reproduces the same seeded negatives, while repeated eager calls
        # still advance
        from . import default_main_program

        prog = default_main_program()
        idx = getattr(prog, "_nce_counter", 0) + 1
        prog._nce_counter = idx
        key = jax.random.fold_in(jax.random.PRNGKey(seed), idx)
    else:
        key = next_key()
    from ..framework.core import Tensor as _T

    def _nce(x, lab, w, b, key, num_neg_samples, num_total_classes):
        neg = jax.random.randint(key, (num_neg_samples,), 0,
                                 num_total_classes)
        lab = lab.reshape(-1)
        pos_logit = jnp.sum(x * w[lab], -1) + b[lab]
        neg_logit = x @ w[neg].T + b[neg]              # [B, S]
        # P(noise) = 1/num_total_classes under the uniform sampler
        log_noise = jnp.log(jnp.asarray(
            num_neg_samples / num_total_classes, x.dtype))
        pos = jax.nn.softplus(-(pos_logit - log_noise))
        negl = jax.nn.softplus(neg_logit - log_noise)
        return (pos + jnp.sum(negl, -1))[:, None]

    return apply_op(_nce, input, label, w, b, _T(key),
                    num_neg_samples=int(num_neg_samples),
                    num_total_classes=int(num_total_classes), op_name="nce")


def multi_box_head(inputs, image, base_size, num_classes, aspect_ratios,
                   min_ratio=None, max_ratio=None, min_sizes=None,
                   max_sizes=None, steps=None, step_w=None, step_h=None,
                   offset=0.5, variance=(0.1, 0.1, 0.2, 0.2), flip=True,
                   clip=False, kernel_size=1, pad=0, stride=1, name=None,
                   min_max_aspect_ratios_order=False):
    """SSD detection head (reference detection/multi_box_head in
    fluid/layers/detection.py): per feature map, conv loc/conf predictions
    + prior boxes; outputs concatenated (mbox_locs [N,M,4], mbox_confs
    [N,M,C], prior_boxes [M,4], variances [M,4])."""
    import numpy as np

    from .. import tensor as T
    from ..vision.ops import prior_box

    if min_sizes is None:
        # reference ratio schedule (detection.py multi_box_head)
        num_layer = len(inputs)
        min_sizes, max_sizes = [], []
        # reference ratio schedule needs >=3 maps; with fewer, span the
        # [min_ratio, max_ratio] range directly
        step = (int(np.floor((max_ratio - min_ratio) / (num_layer - 2)))
                if num_layer > 2 else (max_ratio - min_ratio))
        for ratio in range(min_ratio, max_ratio + 1, max(step, 1)):
            min_sizes.append(base_size * ratio / 100.0)
            max_sizes.append(base_size * (ratio + step) / 100.0)
        min_sizes = ([base_size * 0.10] + min_sizes)[:num_layer]
        max_sizes = ([base_size * 0.20] + max_sizes)[:num_layer]

    locs, confs, boxes, vars_ = [], [], [], []
    for i, feat in enumerate(inputs):
        ar = aspect_ratios[i] if isinstance(aspect_ratios[i], (list, tuple)) \
            else [aspect_ratios[i]]
        mins = min_sizes[i] if isinstance(min_sizes[i], (list, tuple)) \
            else [min_sizes[i]]
        maxs = max_sizes[i] if isinstance(max_sizes[i], (list, tuple)) \
            else [max_sizes[i]]
        box, var = prior_box(feat, image, mins, maxs, ar, list(variance),
                             flip=flip, clip=clip,
                             steps=[steps[i], steps[i]] if steps else [0.0, 0.0],
                             offset=offset,
                             min_max_aspect_ratios_order=min_max_aspect_ratios_order)
        num_priors = int(box.shape[0] * box.shape[1] * box.shape[2]) // (
            int(feat.shape[2]) * int(feat.shape[3]))
        loc = conv2d(feat, num_priors * 4, kernel_size, stride=stride,
                     padding=pad)
        conf = conv2d(feat, num_priors * num_classes, kernel_size,
                      stride=stride, padding=pad)
        n = int(feat.shape[0])
        locs.append(T.reshape(T.transpose(loc, [0, 2, 3, 1]), [n, -1, 4]))
        confs.append(T.reshape(T.transpose(conf, [0, 2, 3, 1]),
                               [n, -1, num_classes]))
        boxes.append(T.reshape(box, [-1, 4]))
        vars_.append(T.reshape(var, [-1, 4]))
    return (T.concat(locs, 1), T.concat(confs, 1), T.concat(boxes, 0),
            T.concat(vars_, 0))


def deform_conv2d(x, offset, mask, num_filters, filter_size, stride=1,
                  padding=0, dilation=1, groups=1, deformable_groups=1,
                  im2col_step=1, weight_attr=None, bias_attr=None, name=None):
    name = _uname("dcn", name)
    from ..framework.core import Parameter
    from ..nn import initializer as I
    from ..vision.ops import deform_conv2d as _dc

    k = filter_size if isinstance(filter_size, (list, tuple)) \
        else (filter_size, filter_size)
    cin = int(x.shape[1])
    w = Parameter(I.XavierNormal()((num_filters, cin // groups, k[0], k[1]),
                                   "float32"), name=name + ".w")
    b = None
    if bias_attr is not False:
        b = Parameter(I.Constant(0.0)((num_filters,), "float32"),
                      name=name + ".b")
    return _dc(x, offset, w, bias=b, stride=stride, padding=padding,
               dilation=dilation, deformable_groups=deformable_groups,
               groups=groups, mask=mask)


def py_func(func, x, out, backward_func=None, skip_vars_in_backward_input=None):
    """Host-python op (reference py_func_op.cc) via jax.pure_callback: runs
    ``func`` on host values even under jit. ``out`` is the template
    Tensor(s) declaring result shape/dtype. backward_func is not supported
    — wrap differentiable logic in ops instead (documented refusal; the
    reference runs backward_func only in static autodiff)."""
    import jax
    import numpy as np

    from ..framework.core import Tensor, apply_op

    if backward_func is not None:
        raise NotImplementedError(
            "py_func backward_func is not supported; compose differentiable "
            "ops or use a custom op (utils/custom_op.py)")
    xs = x if isinstance(x, (list, tuple)) else [x]
    outs = out if isinstance(out, (list, tuple)) else [out]
    specs = [jax.ShapeDtypeStruct(tuple(o.shape), np.dtype(str(o.dtype)))
             for o in outs]
    multi = isinstance(out, (list, tuple))

    def _impl(*arrays):
        res = jax.pure_callback(
            lambda *hs: func(*hs) if multi else (func(*hs),), tuple(specs),
            *arrays)
        return tuple(res) if multi else res[0]

    return apply_op(_impl, *xs, op_name="py_func")
