// GENERATED FILE — do not edit.  Produced by tools/gen_cpp_wrappers.py
// from the mxnet_tpu op registry (the analog of the reference's
// cpp-package OpWrapperGenerator.py output).  Each function invokes its
// operator through the C ABI (MXImperativeInvokeByName); inputs are
// NDArrays, typed parameters serialize onto the registry's string
// coercion layer, extra/optional parameters ride the trailing KWArgs.
#ifndef MXTPU_OPS_HPP_
#define MXTPU_OPS_HPP_

#include <string>
#include <vector>

#include "mxtpu_cpp.hpp"

namespace mxtpu {
namespace op {

inline std::vector<NDArray> Activation(
    const std::vector<NDArray> &inputs,
    const std::string & act_type,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["act_type"] = act_type;
  return Invoke("Activation", inputs, kw);
}

inline std::vector<NDArray> BatchNorm(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("BatchNorm", inputs, kw);
}

inline std::vector<NDArray> BilinearSampler(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("BilinearSampler", inputs, kw);
}

inline std::vector<NDArray> BlockGrad(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("BlockGrad", inputs, kw);
}

inline std::vector<NDArray> Cast(
    const std::vector<NDArray> &inputs,
    const std::string & dtype,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["dtype"] = dtype;
  return Invoke("Cast", inputs, kw);
}

inline std::vector<NDArray> Concat(
    const std::vector<NDArray> &inputs,
    int num_args,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["num_args"] = std::to_string(num_args);
  return Invoke("Concat", inputs, kw);
}

inline std::vector<NDArray> Convolution(
    const std::vector<NDArray> &inputs,
    const Shape & kernel,
    int num_filter,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["kernel"] = kernel.str();
  kw["num_filter"] = std::to_string(num_filter);
  return Invoke("Convolution", inputs, kw);
}

inline std::vector<NDArray> Correlation(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("Correlation", inputs, kw);
}

inline std::vector<NDArray> Crop(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("Crop", inputs, kw);
}

inline std::vector<NDArray> Deconvolution(
    const std::vector<NDArray> &inputs,
    const Shape & kernel,
    int num_filter,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["kernel"] = kernel.str();
  kw["num_filter"] = std::to_string(num_filter);
  return Invoke("Deconvolution", inputs, kw);
}

inline std::vector<NDArray> Dropout(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("Dropout", inputs, kw);
}

inline std::vector<NDArray> Embedding(
    const std::vector<NDArray> &inputs,
    int input_dim,
    int output_dim,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["input_dim"] = std::to_string(input_dim);
  kw["output_dim"] = std::to_string(output_dim);
  return Invoke("Embedding", inputs, kw);
}

inline std::vector<NDArray> Flatten(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("Flatten", inputs, kw);
}

inline std::vector<NDArray> FullyConnected(
    const std::vector<NDArray> &inputs,
    int num_hidden,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["num_hidden"] = std::to_string(num_hidden);
  return Invoke("FullyConnected", inputs, kw);
}

inline std::vector<NDArray> GridGenerator(
    const std::vector<NDArray> &inputs,
    const std::string & transform_type,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["transform_type"] = transform_type;
  return Invoke("GridGenerator", inputs, kw);
}

inline std::vector<NDArray> IdentityAttachKLSparseReg(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("IdentityAttachKLSparseReg", inputs, kw);
}

inline std::vector<NDArray> InstanceNorm(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("InstanceNorm", inputs, kw);
}

inline std::vector<NDArray> L2Normalization(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("L2Normalization", inputs, kw);
}

inline std::vector<NDArray> LRN(
    const std::vector<NDArray> &inputs,
    int nsize,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["nsize"] = std::to_string(nsize);
  return Invoke("LRN", inputs, kw);
}

inline std::vector<NDArray> LayerNorm(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("LayerNorm", inputs, kw);
}

inline std::vector<NDArray> LeakyReLU(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("LeakyReLU", inputs, kw);
}

inline std::vector<NDArray> LinearRegressionOutput(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("LinearRegressionOutput", inputs, kw);
}

inline std::vector<NDArray> LogisticRegressionOutput(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("LogisticRegressionOutput", inputs, kw);
}

inline std::vector<NDArray> MAERegressionOutput(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("MAERegressionOutput", inputs, kw);
}

inline std::vector<NDArray> MakeLoss(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("MakeLoss", inputs, kw);
}

inline std::vector<NDArray> MoEExperts(
    const std::vector<NDArray> &inputs,
    int num_experts,
    int experts_held,
    int num_hidden,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["num_experts"] = std::to_string(num_experts);
  kw["experts_held"] = std::to_string(experts_held);
  kw["num_hidden"] = std::to_string(num_hidden);
  return Invoke("MoEExperts", inputs, kw);
}

inline std::vector<NDArray> MoERouter(
    const std::vector<NDArray> &inputs,
    int num_experts,
    int top_k,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["num_experts"] = std::to_string(num_experts);
  kw["top_k"] = std::to_string(top_k);
  return Invoke("MoERouter", inputs, kw);
}

inline std::vector<NDArray> MultiBoxDetection(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("MultiBoxDetection", inputs, kw);
}

inline std::vector<NDArray> MultiBoxPrior(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("MultiBoxPrior", inputs, kw);
}

inline std::vector<NDArray> MultiBoxTarget(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("MultiBoxTarget", inputs, kw);
}

inline std::vector<NDArray> Pad(
    const std::vector<NDArray> &inputs,
    const Shape & pad_width,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["pad_width"] = pad_width.str();
  return Invoke("Pad", inputs, kw);
}

inline std::vector<NDArray> Pooling(
    const std::vector<NDArray> &inputs,
    const Shape & kernel,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["kernel"] = kernel.str();
  return Invoke("Pooling", inputs, kw);
}

inline std::vector<NDArray> Proposal(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("Proposal", inputs, kw);
}

inline std::vector<NDArray> RMSNorm(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("RMSNorm", inputs, kw);
}

inline std::vector<NDArray> RNN(
    const std::vector<NDArray> &inputs,
    int state_size,
    int num_layers,
    const std::string & mode,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["state_size"] = std::to_string(state_size);
  kw["num_layers"] = std::to_string(num_layers);
  kw["mode"] = mode;
  return Invoke("RNN", inputs, kw);
}

inline std::vector<NDArray> ROIPooling(
    const std::vector<NDArray> &inputs,
    const Shape & pooled_size,
    double spatial_scale,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["pooled_size"] = pooled_size.str();
  kw["spatial_scale"] = FloatStr(spatial_scale);
  return Invoke("ROIPooling", inputs, kw);
}

inline std::vector<NDArray> Reshape(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("Reshape", inputs, kw);
}

inline std::vector<NDArray> RotaryEmbedding(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("RotaryEmbedding", inputs, kw);
}

inline std::vector<NDArray> SVMOutput(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("SVMOutput", inputs, kw);
}

inline std::vector<NDArray> SequenceLast(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("SequenceLast", inputs, kw);
}

inline std::vector<NDArray> SequenceMask(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("SequenceMask", inputs, kw);
}

inline std::vector<NDArray> SequenceReverse(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("SequenceReverse", inputs, kw);
}

inline std::vector<NDArray> SliceChannel(
    const std::vector<NDArray> &inputs,
    int num_outputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["num_outputs"] = std::to_string(num_outputs);
  return Invoke("SliceChannel", inputs, kw);
}

inline std::vector<NDArray> SoftmaxActivation(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("SoftmaxActivation", inputs, kw);
}

inline std::vector<NDArray> SoftmaxOutput(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("SoftmaxOutput", inputs, kw);
}

inline std::vector<NDArray> SpatialTransformer(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("SpatialTransformer", inputs, kw);
}

inline std::vector<NDArray> SwapAxis(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("SwapAxis", inputs, kw);
}

inline std::vector<NDArray> UpSampling(
    const std::vector<NDArray> &inputs,
    int scale,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["scale"] = std::to_string(scale);
  return Invoke("UpSampling", inputs, kw);
}

inline std::vector<NDArray> WarpCTC(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("WarpCTC", inputs, kw);
}

inline std::vector<NDArray> _CrossDeviceCopy(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_CrossDeviceCopy", inputs, kw);
}

inline std::vector<NDArray> _arange(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_arange", inputs, kw);
}

inline std::vector<NDArray> _contrib_DotProductAttention(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_contrib_DotProductAttention", inputs, kw);
}

inline std::vector<NDArray> _contrib_ExitDistribution(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_contrib_ExitDistribution", inputs, kw);
}

inline std::vector<NDArray> _contrib_GatedDeltaRule(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_contrib_GatedDeltaRule", inputs, kw);
}

inline std::vector<NDArray> _contrib_RowCrossEntropy(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_contrib_RowCrossEntropy", inputs, kw);
}

inline std::vector<NDArray> _contrib_ShortConv(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_contrib_ShortConv", inputs, kw);
}

inline std::vector<NDArray> _div(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_div", inputs, kw);
}

inline std::vector<NDArray> _div_scalar(
    const std::vector<NDArray> &inputs,
    double scalar,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["scalar"] = FloatStr(scalar);
  return Invoke("_div_scalar", inputs, kw);
}

inline std::vector<NDArray> _equal(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_equal", inputs, kw);
}

inline std::vector<NDArray> _equal_scalar(
    const std::vector<NDArray> &inputs,
    double scalar,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["scalar"] = FloatStr(scalar);
  return Invoke("_equal_scalar", inputs, kw);
}

inline std::vector<NDArray> _full(
    const std::vector<NDArray> &inputs,
    const Shape & shape,
    double value,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["shape"] = shape.str();
  kw["value"] = FloatStr(value);
  return Invoke("_full", inputs, kw);
}

inline std::vector<NDArray> _greater(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_greater", inputs, kw);
}

inline std::vector<NDArray> _greater_equal(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_greater_equal", inputs, kw);
}

inline std::vector<NDArray> _greater_equal_scalar(
    const std::vector<NDArray> &inputs,
    double scalar,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["scalar"] = FloatStr(scalar);
  return Invoke("_greater_equal_scalar", inputs, kw);
}

inline std::vector<NDArray> _greater_scalar(
    const std::vector<NDArray> &inputs,
    double scalar,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["scalar"] = FloatStr(scalar);
  return Invoke("_greater_scalar", inputs, kw);
}

inline std::vector<NDArray> _hypot(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_hypot", inputs, kw);
}

inline std::vector<NDArray> _hypot_scalar(
    const std::vector<NDArray> &inputs,
    double scalar,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["scalar"] = FloatStr(scalar);
  return Invoke("_hypot_scalar", inputs, kw);
}

inline std::vector<NDArray> _identity_with_attr_like_rhs(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_identity_with_attr_like_rhs", inputs, kw);
}

inline std::vector<NDArray> _imdecode(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_imdecode", inputs, kw);
}

inline std::vector<NDArray> _lesser(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_lesser", inputs, kw);
}

inline std::vector<NDArray> _lesser_equal(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_lesser_equal", inputs, kw);
}

inline std::vector<NDArray> _lesser_equal_scalar(
    const std::vector<NDArray> &inputs,
    double scalar,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["scalar"] = FloatStr(scalar);
  return Invoke("_lesser_equal_scalar", inputs, kw);
}

inline std::vector<NDArray> _lesser_scalar(
    const std::vector<NDArray> &inputs,
    double scalar,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["scalar"] = FloatStr(scalar);
  return Invoke("_lesser_scalar", inputs, kw);
}

inline std::vector<NDArray> _maximum(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_maximum", inputs, kw);
}

inline std::vector<NDArray> _maximum_scalar(
    const std::vector<NDArray> &inputs,
    double scalar,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["scalar"] = FloatStr(scalar);
  return Invoke("_maximum_scalar", inputs, kw);
}

inline std::vector<NDArray> _minimum(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_minimum", inputs, kw);
}

inline std::vector<NDArray> _minimum_scalar(
    const std::vector<NDArray> &inputs,
    double scalar,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["scalar"] = FloatStr(scalar);
  return Invoke("_minimum_scalar", inputs, kw);
}

inline std::vector<NDArray> _minus(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_minus", inputs, kw);
}

inline std::vector<NDArray> _minus_scalar(
    const std::vector<NDArray> &inputs,
    double scalar,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["scalar"] = FloatStr(scalar);
  return Invoke("_minus_scalar", inputs, kw);
}

inline std::vector<NDArray> _mod(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_mod", inputs, kw);
}

inline std::vector<NDArray> _mod_scalar(
    const std::vector<NDArray> &inputs,
    double scalar,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["scalar"] = FloatStr(scalar);
  return Invoke("_mod_scalar", inputs, kw);
}

inline std::vector<NDArray> _mul(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_mul", inputs, kw);
}

inline std::vector<NDArray> _mul_scalar(
    const std::vector<NDArray> &inputs,
    double scalar,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["scalar"] = FloatStr(scalar);
  return Invoke("_mul_scalar", inputs, kw);
}

inline std::vector<NDArray> _not_equal(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_not_equal", inputs, kw);
}

inline std::vector<NDArray> _not_equal_scalar(
    const std::vector<NDArray> &inputs,
    double scalar,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["scalar"] = FloatStr(scalar);
  return Invoke("_not_equal_scalar", inputs, kw);
}

inline std::vector<NDArray> _ones(
    const std::vector<NDArray> &inputs,
    const Shape & shape,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["shape"] = shape.str();
  return Invoke("_ones", inputs, kw);
}

inline std::vector<NDArray> _plus(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_plus", inputs, kw);
}

inline std::vector<NDArray> _plus_scalar(
    const std::vector<NDArray> &inputs,
    double scalar,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["scalar"] = FloatStr(scalar);
  return Invoke("_plus_scalar", inputs, kw);
}

inline std::vector<NDArray> _power(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_power", inputs, kw);
}

inline std::vector<NDArray> _power_scalar(
    const std::vector<NDArray> &inputs,
    double scalar,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["scalar"] = FloatStr(scalar);
  return Invoke("_power_scalar", inputs, kw);
}

inline std::vector<NDArray> _rdiv_scalar(
    const std::vector<NDArray> &inputs,
    double scalar,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["scalar"] = FloatStr(scalar);
  return Invoke("_rdiv_scalar", inputs, kw);
}

inline std::vector<NDArray> _rminus_scalar(
    const std::vector<NDArray> &inputs,
    double scalar,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["scalar"] = FloatStr(scalar);
  return Invoke("_rminus_scalar", inputs, kw);
}

inline std::vector<NDArray> _rmod_scalar(
    const std::vector<NDArray> &inputs,
    double scalar,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["scalar"] = FloatStr(scalar);
  return Invoke("_rmod_scalar", inputs, kw);
}

inline std::vector<NDArray> _rpower_scalar(
    const std::vector<NDArray> &inputs,
    double scalar,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["scalar"] = FloatStr(scalar);
  return Invoke("_rpower_scalar", inputs, kw);
}

inline std::vector<NDArray> _sample_exponential(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_sample_exponential", inputs, kw);
}

inline std::vector<NDArray> _sample_gamma(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_sample_gamma", inputs, kw);
}

inline std::vector<NDArray> _sample_gennegbinomial(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_sample_gennegbinomial", inputs, kw);
}

inline std::vector<NDArray> _sample_negbinomial(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_sample_negbinomial", inputs, kw);
}

inline std::vector<NDArray> _sample_normal(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_sample_normal", inputs, kw);
}

inline std::vector<NDArray> _sample_poisson(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_sample_poisson", inputs, kw);
}

inline std::vector<NDArray> _sample_uniform(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_sample_uniform", inputs, kw);
}

inline std::vector<NDArray> _zeros(
    const std::vector<NDArray> &inputs,
    const Shape & shape,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["shape"] = shape.str();
  return Invoke("_zeros", inputs, kw);
}

inline std::vector<NDArray> abs(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("abs", inputs, kw);
}

inline std::vector<NDArray> adam_update(
    const std::vector<NDArray> &inputs,
    double lr,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["lr"] = FloatStr(lr);
  return Invoke("adam_update", inputs, kw);
}

inline std::vector<NDArray> add_n(
    const std::vector<NDArray> &inputs,
    int num_args,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["num_args"] = std::to_string(num_args);
  return Invoke("add_n", inputs, kw);
}

inline std::vector<NDArray> arccos(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("arccos", inputs, kw);
}

inline std::vector<NDArray> arccosh(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("arccosh", inputs, kw);
}

inline std::vector<NDArray> arcsin(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("arcsin", inputs, kw);
}

inline std::vector<NDArray> arcsinh(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("arcsinh", inputs, kw);
}

inline std::vector<NDArray> arctan(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("arctan", inputs, kw);
}

inline std::vector<NDArray> arctanh(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("arctanh", inputs, kw);
}

inline std::vector<NDArray> argmax(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("argmax", inputs, kw);
}

inline std::vector<NDArray> argmax_channel(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("argmax_channel", inputs, kw);
}

inline std::vector<NDArray> argmin(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("argmin", inputs, kw);
}

inline std::vector<NDArray> argsort(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("argsort", inputs, kw);
}

inline std::vector<NDArray> batch_dot(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("batch_dot", inputs, kw);
}

inline std::vector<NDArray> batch_take(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("batch_take", inputs, kw);
}

inline std::vector<NDArray> broadcast_add(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("broadcast_add", inputs, kw);
}

inline std::vector<NDArray> broadcast_axis(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("broadcast_axis", inputs, kw);
}

inline std::vector<NDArray> broadcast_div(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("broadcast_div", inputs, kw);
}

inline std::vector<NDArray> broadcast_equal(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("broadcast_equal", inputs, kw);
}

inline std::vector<NDArray> broadcast_greater(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("broadcast_greater", inputs, kw);
}

inline std::vector<NDArray> broadcast_greater_equal(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("broadcast_greater_equal", inputs, kw);
}

inline std::vector<NDArray> broadcast_hypot(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("broadcast_hypot", inputs, kw);
}

inline std::vector<NDArray> broadcast_lesser(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("broadcast_lesser", inputs, kw);
}

inline std::vector<NDArray> broadcast_lesser_equal(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("broadcast_lesser_equal", inputs, kw);
}

inline std::vector<NDArray> broadcast_maximum(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("broadcast_maximum", inputs, kw);
}

inline std::vector<NDArray> broadcast_minimum(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("broadcast_minimum", inputs, kw);
}

inline std::vector<NDArray> broadcast_mod(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("broadcast_mod", inputs, kw);
}

inline std::vector<NDArray> broadcast_mul(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("broadcast_mul", inputs, kw);
}

inline std::vector<NDArray> broadcast_not_equal(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("broadcast_not_equal", inputs, kw);
}

inline std::vector<NDArray> broadcast_power(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("broadcast_power", inputs, kw);
}

inline std::vector<NDArray> broadcast_sub(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("broadcast_sub", inputs, kw);
}

inline std::vector<NDArray> broadcast_to(
    const std::vector<NDArray> &inputs,
    const Shape & shape,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["shape"] = shape.str();
  return Invoke("broadcast_to", inputs, kw);
}

inline std::vector<NDArray> cbrt(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("cbrt", inputs, kw);
}

inline std::vector<NDArray> ceil(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("ceil", inputs, kw);
}

inline std::vector<NDArray> clip(
    const std::vector<NDArray> &inputs,
    double a_min,
    double a_max,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["a_min"] = FloatStr(a_min);
  kw["a_max"] = FloatStr(a_max);
  return Invoke("clip", inputs, kw);
}

inline std::vector<NDArray> cos(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("cos", inputs, kw);
}

inline std::vector<NDArray> cosh(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("cosh", inputs, kw);
}

inline std::vector<NDArray> count_sketch(
    const std::vector<NDArray> &inputs,
    int out_dim,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["out_dim"] = std::to_string(out_dim);
  return Invoke("count_sketch", inputs, kw);
}

inline std::vector<NDArray> degrees(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("degrees", inputs, kw);
}

inline std::vector<NDArray> dot(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("dot", inputs, kw);
}

inline std::vector<NDArray> erf(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("erf", inputs, kw);
}

inline std::vector<NDArray> exp(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("exp", inputs, kw);
}

inline std::vector<NDArray> expand_dims(
    const std::vector<NDArray> &inputs,
    int axis,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["axis"] = std::to_string(axis);
  return Invoke("expand_dims", inputs, kw);
}

inline std::vector<NDArray> expm1(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("expm1", inputs, kw);
}

inline std::vector<NDArray> fft(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("fft", inputs, kw);
}

inline std::vector<NDArray> fix(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("fix", inputs, kw);
}

inline std::vector<NDArray> floor(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("floor", inputs, kw);
}

inline std::vector<NDArray> gamma(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("gamma", inputs, kw);
}

inline std::vector<NDArray> gammaln(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("gammaln", inputs, kw);
}

inline std::vector<NDArray> identity(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("identity", inputs, kw);
}

inline std::vector<NDArray> ifft(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("ifft", inputs, kw);
}

inline std::vector<NDArray> log(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("log", inputs, kw);
}

inline std::vector<NDArray> log10(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("log10", inputs, kw);
}

inline std::vector<NDArray> log1p(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("log1p", inputs, kw);
}

inline std::vector<NDArray> log2(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("log2", inputs, kw);
}

inline std::vector<NDArray> log_softmax(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("log_softmax", inputs, kw);
}

inline std::vector<NDArray> make_loss_internal(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("make_loss_internal", inputs, kw);
}

inline std::vector<NDArray> max(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("max", inputs, kw);
}

inline std::vector<NDArray> mean(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("mean", inputs, kw);
}

inline std::vector<NDArray> min(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("min", inputs, kw);
}

inline std::vector<NDArray> nanprod(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("nanprod", inputs, kw);
}

inline std::vector<NDArray> nansum(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("nansum", inputs, kw);
}

inline std::vector<NDArray> negative(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("negative", inputs, kw);
}

inline std::vector<NDArray> norm(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("norm", inputs, kw);
}

inline std::vector<NDArray> one_hot(
    const std::vector<NDArray> &inputs,
    int depth,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["depth"] = std::to_string(depth);
  return Invoke("one_hot", inputs, kw);
}

inline std::vector<NDArray> ones_like(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("ones_like", inputs, kw);
}

inline std::vector<NDArray> pick(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("pick", inputs, kw);
}

inline std::vector<NDArray> prod(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("prod", inputs, kw);
}

inline std::vector<NDArray> radians(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("radians", inputs, kw);
}

inline std::vector<NDArray> rcbrt(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("rcbrt", inputs, kw);
}

inline std::vector<NDArray> reciprocal(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("reciprocal", inputs, kw);
}

inline std::vector<NDArray> relu(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("relu", inputs, kw);
}

inline std::vector<NDArray> repeat(
    const std::vector<NDArray> &inputs,
    int repeats,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["repeats"] = std::to_string(repeats);
  return Invoke("repeat", inputs, kw);
}

inline std::vector<NDArray> reverse(
    const std::vector<NDArray> &inputs,
    const std::string & axis,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["axis"] = axis;
  return Invoke("reverse", inputs, kw);
}

inline std::vector<NDArray> rint(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("rint", inputs, kw);
}

inline std::vector<NDArray> rmsprop_update(
    const std::vector<NDArray> &inputs,
    double lr,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["lr"] = FloatStr(lr);
  return Invoke("rmsprop_update", inputs, kw);
}

inline std::vector<NDArray> rmspropalex_update(
    const std::vector<NDArray> &inputs,
    double lr,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["lr"] = FloatStr(lr);
  return Invoke("rmspropalex_update", inputs, kw);
}

inline std::vector<NDArray> round(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("round", inputs, kw);
}

inline std::vector<NDArray> rsqrt(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("rsqrt", inputs, kw);
}

inline std::vector<NDArray> sgd_mom_update(
    const std::vector<NDArray> &inputs,
    double lr,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["lr"] = FloatStr(lr);
  return Invoke("sgd_mom_update", inputs, kw);
}

inline std::vector<NDArray> sgd_update(
    const std::vector<NDArray> &inputs,
    double lr,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["lr"] = FloatStr(lr);
  return Invoke("sgd_update", inputs, kw);
}

inline std::vector<NDArray> sigmoid(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("sigmoid", inputs, kw);
}

inline std::vector<NDArray> sign(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("sign", inputs, kw);
}

inline std::vector<NDArray> sin(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("sin", inputs, kw);
}

inline std::vector<NDArray> sinh(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("sinh", inputs, kw);
}

inline std::vector<NDArray> slice(
    const std::vector<NDArray> &inputs,
    const Shape & begin,
    const Shape & end,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["begin"] = begin.str();
  kw["end"] = end.str();
  return Invoke("slice", inputs, kw);
}

inline std::vector<NDArray> slice_axis(
    const std::vector<NDArray> &inputs,
    int axis,
    int begin,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["axis"] = std::to_string(axis);
  kw["begin"] = std::to_string(begin);
  return Invoke("slice_axis", inputs, kw);
}

inline std::vector<NDArray> smooth_l1(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("smooth_l1", inputs, kw);
}

inline std::vector<NDArray> softmax(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("softmax", inputs, kw);
}

inline std::vector<NDArray> softmax_cross_entropy(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("softmax_cross_entropy", inputs, kw);
}

inline std::vector<NDArray> softrelu(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("softrelu", inputs, kw);
}

inline std::vector<NDArray> sort(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("sort", inputs, kw);
}

inline std::vector<NDArray> sqrt(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("sqrt", inputs, kw);
}

inline std::vector<NDArray> square(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("square", inputs, kw);
}

inline std::vector<NDArray> sum(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("sum", inputs, kw);
}

inline std::vector<NDArray> take(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("take", inputs, kw);
}

inline std::vector<NDArray> tan(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("tan", inputs, kw);
}

inline std::vector<NDArray> tanh(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("tanh", inputs, kw);
}

inline std::vector<NDArray> tile(
    const std::vector<NDArray> &inputs,
    const Shape & reps,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["reps"] = reps.str();
  return Invoke("tile", inputs, kw);
}

inline std::vector<NDArray> topk(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("topk", inputs, kw);
}

inline std::vector<NDArray> transpose(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("transpose", inputs, kw);
}

inline std::vector<NDArray> trunc(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("trunc", inputs, kw);
}

inline std::vector<NDArray> where(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("where", inputs, kw);
}

inline std::vector<NDArray> zeros_like(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("zeros_like", inputs, kw);
}

// ---- aliases ----
inline std::vector<NDArray> Convolution_v1(
    const std::vector<NDArray> &inputs,
    const Shape & kernel,
    int num_filter,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["kernel"] = kernel.str();
  kw["num_filter"] = std::to_string(num_filter);
  return Invoke("Convolution_v1", inputs, kw);
}

inline std::vector<NDArray> ElementWiseSum(
    const std::vector<NDArray> &inputs,
    int num_args,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["num_args"] = std::to_string(num_args);
  return Invoke("ElementWiseSum", inputs, kw);
}

inline std::vector<NDArray> Pooling_v1(
    const std::vector<NDArray> &inputs,
    const Shape & kernel,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["kernel"] = kernel.str();
  return Invoke("Pooling_v1", inputs, kw);
}

inline std::vector<NDArray> Softmax(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("Softmax", inputs, kw);
}

inline std::vector<NDArray> _Div(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_Div", inputs, kw);
}

inline std::vector<NDArray> _Minus(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_Minus", inputs, kw);
}

inline std::vector<NDArray> _Mul(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_Mul", inputs, kw);
}

inline std::vector<NDArray> _Plus(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_Plus", inputs, kw);
}

inline std::vector<NDArray> _add(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_add", inputs, kw);
}

inline std::vector<NDArray> _contrib_MultiBoxDetection(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_contrib_MultiBoxDetection", inputs, kw);
}

inline std::vector<NDArray> _contrib_MultiBoxPrior(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_contrib_MultiBoxPrior", inputs, kw);
}

inline std::vector<NDArray> _contrib_MultiBoxTarget(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_contrib_MultiBoxTarget", inputs, kw);
}

inline std::vector<NDArray> _contrib_Proposal(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_contrib_Proposal", inputs, kw);
}

inline std::vector<NDArray> _contrib_count_sketch(
    const std::vector<NDArray> &inputs,
    int out_dim,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["out_dim"] = std::to_string(out_dim);
  return Invoke("_contrib_count_sketch", inputs, kw);
}

inline std::vector<NDArray> _contrib_fft(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_contrib_fft", inputs, kw);
}

inline std::vector<NDArray> _contrib_ifft(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_contrib_ifft", inputs, kw);
}

inline std::vector<NDArray> _copy(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_copy", inputs, kw);
}

inline std::vector<NDArray> _grad_add(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_grad_add", inputs, kw);
}

inline std::vector<NDArray> _random_normal(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_random_normal", inputs, kw);
}

inline std::vector<NDArray> _random_uniform(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_random_uniform", inputs, kw);
}

inline std::vector<NDArray> _sub(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("_sub", inputs, kw);
}

inline std::vector<NDArray> _sum_n(
    const std::vector<NDArray> &inputs,
    int num_args,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["num_args"] = std::to_string(num_args);
  return Invoke("_sum_n", inputs, kw);
}

inline std::vector<NDArray> broadcast_axes(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("broadcast_axes", inputs, kw);
}

inline std::vector<NDArray> cast(
    const std::vector<NDArray> &inputs,
    const std::string & dtype,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["dtype"] = dtype;
  return Invoke("cast", inputs, kw);
}

inline std::vector<NDArray> concat(
    const std::vector<NDArray> &inputs,
    int num_args,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["num_args"] = std::to_string(num_args);
  return Invoke("concat", inputs, kw);
}

inline std::vector<NDArray> elemwise_add(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("elemwise_add", inputs, kw);
}

inline std::vector<NDArray> elemwise_div(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("elemwise_div", inputs, kw);
}

inline std::vector<NDArray> elemwise_mul(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("elemwise_mul", inputs, kw);
}

inline std::vector<NDArray> elemwise_sub(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("elemwise_sub", inputs, kw);
}

inline std::vector<NDArray> exponential(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("exponential", inputs, kw);
}

inline std::vector<NDArray> flatten(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("flatten", inputs, kw);
}

inline std::vector<NDArray> flip(
    const std::vector<NDArray> &inputs,
    const std::string & axis,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["axis"] = axis;
  return Invoke("flip", inputs, kw);
}

inline std::vector<NDArray> full(
    const std::vector<NDArray> &inputs,
    const Shape & shape,
    double value,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["shape"] = shape.str();
  kw["value"] = FloatStr(value);
  return Invoke("full", inputs, kw);
}

inline std::vector<NDArray> generalized_negative_binomial(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("generalized_negative_binomial", inputs, kw);
}

inline std::vector<NDArray> max_axis(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("max_axis", inputs, kw);
}

inline std::vector<NDArray> min_axis(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("min_axis", inputs, kw);
}

inline std::vector<NDArray> negative_binomial(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("negative_binomial", inputs, kw);
}

inline std::vector<NDArray> normal(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("normal", inputs, kw);
}

inline std::vector<NDArray> ones(
    const std::vector<NDArray> &inputs,
    const Shape & shape,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["shape"] = shape.str();
  return Invoke("ones", inputs, kw);
}

inline std::vector<NDArray> pad(
    const std::vector<NDArray> &inputs,
    const Shape & pad_width,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["pad_width"] = pad_width.str();
  return Invoke("pad", inputs, kw);
}

inline std::vector<NDArray> poisson(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("poisson", inputs, kw);
}

inline std::vector<NDArray> random_exponential(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("random_exponential", inputs, kw);
}

inline std::vector<NDArray> random_gamma(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("random_gamma", inputs, kw);
}

inline std::vector<NDArray> random_generalized_negative_binomial(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("random_generalized_negative_binomial", inputs, kw);
}

inline std::vector<NDArray> random_negative_binomial(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("random_negative_binomial", inputs, kw);
}

inline std::vector<NDArray> random_normal(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("random_normal", inputs, kw);
}

inline std::vector<NDArray> random_poisson(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("random_poisson", inputs, kw);
}

inline std::vector<NDArray> random_uniform(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("random_uniform", inputs, kw);
}

inline std::vector<NDArray> reshape(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("reshape", inputs, kw);
}

inline std::vector<NDArray> split(
    const std::vector<NDArray> &inputs,
    int num_outputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["num_outputs"] = std::to_string(num_outputs);
  return Invoke("split", inputs, kw);
}

inline std::vector<NDArray> stop_gradient(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("stop_gradient", inputs, kw);
}

inline std::vector<NDArray> sum_axis(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("sum_axis", inputs, kw);
}

inline std::vector<NDArray> swapaxes(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("swapaxes", inputs, kw);
}

inline std::vector<NDArray> uniform(
    const std::vector<NDArray> &inputs,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  return Invoke("uniform", inputs, kw);
}

inline std::vector<NDArray> zeros(
    const std::vector<NDArray> &inputs,
    const Shape & shape,
    const KWArgs &extra = {}) {
  KWArgs kw(extra);
  kw["shape"] = shape.str();
  return Invoke("zeros", inputs, kw);
}

}  // namespace op
}  // namespace mxtpu

#endif  // MXTPU_OPS_HPP_
