// op.h — GENERATED per-op C++ wrappers over the packed FFI.
// Regenerate: python cpp-package/scripts/op_wrapper_generator.py
// (reference analog: cpp-package/scripts/OpWrapperGenerator.py ->
//  mxnet-cpp/op.h). Do not edit by hand.
#pragma once

#include <sstream>
#include <string>
#include <vector>

#include "py_runtime.hpp"

namespace mxtpu {
namespace op {
namespace detail {

class JsonBuilder {
 public:
  void put_bool(const std::string& k, bool v) {
    add(k, v ? "true" : "false");
  }
  void put_int(const std::string& k, long long v) {
    add(k, std::to_string(v));
  }
  void put_num(const std::string& k, double v) {
    std::ostringstream os;
    os.precision(17);
    os << v;
    add(k, os.str());
  }
  void put_str(const std::string& k, const std::string& v) {
    std::string e;
    for (char c : v) {
      if (c == '"' || c == '\\') e += '\\';
      e += c;
    }
    add(k, "\"" + e + "\"");
  }
  void put_ivec(const std::string& k, const std::vector<long long>& v) {
    std::string s = "[";
    for (size_t i = 0; i < v.size(); ++i) {
      if (i) s += ", ";
      s += std::to_string(v[i]);
    }
    add(k, s + "]");
  }
  void put_fvec(const std::string& k, const std::vector<double>& v) {
    std::string s = "[";
    for (size_t i = 0; i < v.size(); ++i) {
      if (i) s += ", ";
      std::ostringstream os;
      os.precision(17);
      os << v[i];
      s += os.str();
    }
    add(k, s + "]");
  }
  void raw(const std::string& k, const std::string& json) { add(k, json); }
  std::string str() const { return "{" + body_ + "}"; }

 private:
  void add(const std::string& k, const std::string& v) {
    if (!body_.empty()) body_ += ", ";
    body_ += "\"" + k + "\": " + v;
  }
  std::string body_;
};

inline std::string merge(const std::string& a, const std::string& b) {
  // shallow-merge two JSON objects emitted by JsonBuilder
  if (b.empty() || b == "{}") return a;
  if (a == "{}") return b;
  return a.substr(0, a.size() - 1) + ", " + b.substr(1);
}

}  // namespace detail


inline std::vector<PackedTensor> Activation(
    PyRuntime& rt,
    const PackedTensor& data,
    const std::string& act_type = "relu") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  a_.put_str("act_type", act_type);
  return rt.invoke("Activation", ins_, a_.str());
}

inline std::vector<PackedTensor> BatchNorm(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& gamma,
    const PackedTensor& beta,
    const PackedTensor& moving_mean,
    const PackedTensor& moving_var,
    double eps = 0.001,
    double momentum = 0.9,
    bool fix_gamma = true,
    bool use_global_stats = false,
    bool output_mean_var = false,
    long long axis = 1,
    const char* cudnn_off_json = nullptr,
    const char* min_calib_range_json = nullptr,
    const char* max_calib_range_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(gamma);
  ins_.push_back(beta);
  ins_.push_back(moving_mean);
  ins_.push_back(moving_var);
  detail::JsonBuilder a_;
  a_.put_num("eps", eps);
  a_.put_num("momentum", momentum);
  a_.put_bool("fix_gamma", fix_gamma);
  a_.put_bool("use_global_stats", use_global_stats);
  a_.put_bool("output_mean_var", output_mean_var);
  a_.put_int("axis", axis);
  if (cudnn_off_json) a_.raw("cudnn_off", cudnn_off_json);
  if (min_calib_range_json) a_.raw("min_calib_range", min_calib_range_json);
  if (max_calib_range_json) a_.raw("max_calib_range", max_calib_range_json);
  return rt.invoke("BatchNorm", ins_, a_.str());
}

inline std::vector<PackedTensor> BilinearSampler(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& grid,
    const char* cudnn_off_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(grid);
  detail::JsonBuilder a_;
  if (cudnn_off_json) a_.raw("cudnn_off", cudnn_off_json);
  return rt.invoke("BilinearSampler", ins_, a_.str());
}

inline std::vector<PackedTensor> BlockGrad(
    PyRuntime& rt,
    const PackedTensor& data) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  return rt.invoke("BlockGrad", ins_, a_.str());
}

inline std::vector<PackedTensor> CTCLoss(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& label,
    const char* data_lengths_json = nullptr,
    const char* label_lengths_json = nullptr,
    bool use_data_lengths = false,
    bool use_label_lengths = false,
    const std::string& blank_label = "first") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(label);
  detail::JsonBuilder a_;
  if (data_lengths_json) a_.raw("data_lengths", data_lengths_json);
  if (label_lengths_json) a_.raw("label_lengths", label_lengths_json);
  a_.put_bool("use_data_lengths", use_data_lengths);
  a_.put_bool("use_label_lengths", use_label_lengths);
  a_.put_str("blank_label", blank_label);
  return rt.invoke("CTCLoss", ins_, a_.str());
}

inline std::vector<PackedTensor> Cast(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& dtype) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(dtype);
  detail::JsonBuilder a_;
  return rt.invoke("Cast", ins_, a_.str());
}

inline std::vector<PackedTensor> Concat(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    long long dim = 1,
    const char* num_args_json = nullptr) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  a_.put_int("dim", dim);
  if (num_args_json) a_.raw("num_args", num_args_json);
  return rt.invoke("Concat", ins_, a_.str());
}

inline std::vector<PackedTensor> Convolution(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& weight,
    const PackedTensor* bias = nullptr,
    const char* kernel_json = nullptr,
    const char* stride_json = nullptr,
    const char* pad_json = nullptr,
    const char* dilate_json = nullptr,
    const char* num_filter_json = nullptr,
    long long num_group = 1,
    bool no_bias = false,
    const char* workspace_json = nullptr,
    const char* cudnn_tune_json = nullptr,
    const char* cudnn_off_json = nullptr,
    const char* layout_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(weight);
  if (bias) ins_.push_back(*bias);
  detail::JsonBuilder a_;
  if (kernel_json) a_.raw("kernel", kernel_json);
  if (stride_json) a_.raw("stride", stride_json);
  if (pad_json) a_.raw("pad", pad_json);
  if (dilate_json) a_.raw("dilate", dilate_json);
  if (num_filter_json) a_.raw("num_filter", num_filter_json);
  a_.put_int("num_group", num_group);
  a_.put_bool("no_bias", no_bias);
  if (workspace_json) a_.raw("workspace", workspace_json);
  if (cudnn_tune_json) a_.raw("cudnn_tune", cudnn_tune_json);
  if (cudnn_off_json) a_.raw("cudnn_off", cudnn_off_json);
  if (layout_json) a_.raw("layout", layout_json);
  return rt.invoke("Convolution", ins_, a_.str());
}

inline std::vector<PackedTensor> Correlation(
    PyRuntime& rt,
    const PackedTensor& data1,
    const PackedTensor& data2,
    long long kernel_size = 1,
    long long max_displacement = 1,
    long long stride1 = 1,
    long long stride2 = 1,
    long long pad_size = 0,
    bool is_multiply = true) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data1);
  ins_.push_back(data2);
  detail::JsonBuilder a_;
  a_.put_int("kernel_size", kernel_size);
  a_.put_int("max_displacement", max_displacement);
  a_.put_int("stride1", stride1);
  a_.put_int("stride2", stride2);
  a_.put_int("pad_size", pad_size);
  a_.put_bool("is_multiply", is_multiply);
  return rt.invoke("Correlation", ins_, a_.str());
}

inline std::vector<PackedTensor> Crop(
    PyRuntime& rt,
    const PackedTensor& data,
    const char* crop_like_json = nullptr,
    const std::vector<long long>& offset = {0, 0},
    const std::vector<long long>& h_w = {0, 0},
    bool center_crop = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (crop_like_json) a_.raw("crop_like", crop_like_json);
  a_.put_ivec("offset", offset);
  a_.put_ivec("h_w", h_w);
  a_.put_bool("center_crop", center_crop);
  return rt.invoke("Crop", ins_, a_.str());
}

inline std::vector<PackedTensor> Custom(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* op_type_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (op_type_json) a_.raw("op_type", op_type_json);
  return rt.invoke("Custom", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> Deconvolution(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& weight,
    const PackedTensor* bias = nullptr,
    const char* kernel_json = nullptr,
    const char* stride_json = nullptr,
    const char* pad_json = nullptr,
    const char* dilate_json = nullptr,
    const char* adj_json = nullptr,
    const char* target_shape_json = nullptr,
    const char* num_filter_json = nullptr,
    long long num_group = 1,
    bool no_bias = true,
    const char* workspace_json = nullptr,
    const char* cudnn_tune_json = nullptr,
    const char* cudnn_off_json = nullptr,
    const char* layout_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(weight);
  if (bias) ins_.push_back(*bias);
  detail::JsonBuilder a_;
  if (kernel_json) a_.raw("kernel", kernel_json);
  if (stride_json) a_.raw("stride", stride_json);
  if (pad_json) a_.raw("pad", pad_json);
  if (dilate_json) a_.raw("dilate", dilate_json);
  if (adj_json) a_.raw("adj", adj_json);
  if (target_shape_json) a_.raw("target_shape", target_shape_json);
  if (num_filter_json) a_.raw("num_filter", num_filter_json);
  a_.put_int("num_group", num_group);
  a_.put_bool("no_bias", no_bias);
  if (workspace_json) a_.raw("workspace", workspace_json);
  if (cudnn_tune_json) a_.raw("cudnn_tune", cudnn_tune_json);
  if (cudnn_off_json) a_.raw("cudnn_off", cudnn_off_json);
  if (layout_json) a_.raw("layout", layout_json);
  return rt.invoke("Deconvolution", ins_, a_.str());
}

inline std::vector<PackedTensor> DeformableConvolution(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& offset,
    const PackedTensor& weight,
    const PackedTensor* bias = nullptr,
    const std::vector<long long>& kernel = {3, 3},
    const std::vector<long long>& stride = {1, 1},
    const std::vector<long long>& pad = {0, 0},
    const std::vector<long long>& dilate = {1, 1},
    long long num_deformable_group = 1,
    long long groups = 1,
    const char* mask_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(offset);
  ins_.push_back(weight);
  if (bias) ins_.push_back(*bias);
  detail::JsonBuilder a_;
  a_.put_ivec("kernel", kernel);
  a_.put_ivec("stride", stride);
  a_.put_ivec("pad", pad);
  a_.put_ivec("dilate", dilate);
  a_.put_int("num_deformable_group", num_deformable_group);
  a_.put_int("groups", groups);
  if (mask_json) a_.raw("mask", mask_json);
  return rt.invoke("DeformableConvolution", ins_, a_.str());
}

inline std::vector<PackedTensor> Dropout(
    PyRuntime& rt,
    const PackedTensor& data,
    const char* key_json = nullptr,
    double p = 0.5,
    const std::string& mode = "training",
    const char* axes_json = nullptr,
    const char* cudnn_off_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (key_json) a_.raw("key", key_json);
  a_.put_num("p", p);
  a_.put_str("mode", mode);
  if (axes_json) a_.raw("axes", axes_json);
  if (cudnn_off_json) a_.raw("cudnn_off", cudnn_off_json);
  return rt.invoke("Dropout", ins_, a_.str());
}

inline std::vector<PackedTensor> Embedding(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& weight,
    const char* input_dim_json = nullptr,
    const char* output_dim_json = nullptr,
    const char* dtype_json = nullptr,
    bool sparse_grad = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(weight);
  detail::JsonBuilder a_;
  if (input_dim_json) a_.raw("input_dim", input_dim_json);
  if (output_dim_json) a_.raw("output_dim", output_dim_json);
  if (dtype_json) a_.raw("dtype", dtype_json);
  a_.put_bool("sparse_grad", sparse_grad);
  return rt.invoke("Embedding", ins_, a_.str());
}

inline std::vector<PackedTensor> Flatten(
    PyRuntime& rt,
    const PackedTensor& data) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  return rt.invoke("Flatten", ins_, a_.str());
}

inline std::vector<PackedTensor> FullyConnected(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& weight,
    const PackedTensor* bias = nullptr,
    const char* num_hidden_json = nullptr,
    bool no_bias = false,
    bool flatten = true) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(weight);
  if (bias) ins_.push_back(*bias);
  detail::JsonBuilder a_;
  if (num_hidden_json) a_.raw("num_hidden", num_hidden_json);
  a_.put_bool("no_bias", no_bias);
  a_.put_bool("flatten", flatten);
  return rt.invoke("FullyConnected", ins_, a_.str());
}

inline std::vector<PackedTensor> GridGenerator(
    PyRuntime& rt,
    const PackedTensor& data,
    const std::string& transform_type = "affine",
    const char* target_shape_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  a_.put_str("transform_type", transform_type);
  if (target_shape_json) a_.raw("target_shape", target_shape_json);
  return rt.invoke("GridGenerator", ins_, a_.str());
}

inline std::vector<PackedTensor> GroupNorm(
    PyRuntime& rt,
    const PackedTensor& x,
    const PackedTensor& gamma,
    const PackedTensor& beta,
    const PackedTensor& num_groups,
    double eps = 1e-05) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  ins_.push_back(gamma);
  ins_.push_back(beta);
  ins_.push_back(num_groups);
  detail::JsonBuilder a_;
  a_.put_num("eps", eps);
  return rt.invoke("GroupNorm", ins_, a_.str());
}

inline std::vector<PackedTensor> IdentityAttachKLSparseReg(
    PyRuntime& rt,
    const PackedTensor& data,
    double sparseness_target = 0.1,
    double penalty = 0.001,
    double momentum = 0.9) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  a_.put_num("sparseness_target", sparseness_target);
  a_.put_num("penalty", penalty);
  a_.put_num("momentum", momentum);
  return rt.invoke("IdentityAttachKLSparseReg", ins_, a_.str());
}

inline std::vector<PackedTensor> InstanceNorm(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& gamma,
    const PackedTensor& beta,
    double eps = 0.001) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(gamma);
  ins_.push_back(beta);
  detail::JsonBuilder a_;
  a_.put_num("eps", eps);
  return rt.invoke("InstanceNorm", ins_, a_.str());
}

inline std::vector<PackedTensor> L2Normalization(
    PyRuntime& rt,
    const PackedTensor& data,
    double eps = 1e-10,
    const std::string& mode = "instance") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  a_.put_num("eps", eps);
  a_.put_str("mode", mode);
  return rt.invoke("L2Normalization", ins_, a_.str());
}

inline std::vector<PackedTensor> LRN(
    PyRuntime& rt,
    const PackedTensor& data,
    double alpha = 0.0001,
    double beta = 0.75,
    double knorm = 2.0,
    long long nsize = 5) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  a_.put_num("alpha", alpha);
  a_.put_num("beta", beta);
  a_.put_num("knorm", knorm);
  a_.put_int("nsize", nsize);
  return rt.invoke("LRN", ins_, a_.str());
}

inline std::vector<PackedTensor> LayerNorm(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& gamma,
    const PackedTensor& beta,
    long long axis = -1,
    double eps = 1e-05,
    bool output_mean_var = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(gamma);
  ins_.push_back(beta);
  detail::JsonBuilder a_;
  a_.put_int("axis", axis);
  a_.put_num("eps", eps);
  a_.put_bool("output_mean_var", output_mean_var);
  return rt.invoke("LayerNorm", ins_, a_.str());
}

inline std::vector<PackedTensor> LeakyReLU(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor* gamma = nullptr,
    const std::string& act_type = "leaky",
    double slope = 0.25,
    const char* lower_bound_json = nullptr,
    const char* upper_bound_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  if (gamma) ins_.push_back(*gamma);
  detail::JsonBuilder a_;
  a_.put_str("act_type", act_type);
  a_.put_num("slope", slope);
  if (lower_bound_json) a_.raw("lower_bound", lower_bound_json);
  if (upper_bound_json) a_.raw("upper_bound", upper_bound_json);
  return rt.invoke("LeakyReLU", ins_, a_.str());
}

inline std::vector<PackedTensor> LinearRegressionOutput(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& label,
    double grad_scale = 1.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(label);
  detail::JsonBuilder a_;
  a_.put_num("grad_scale", grad_scale);
  return rt.invoke("LinearRegressionOutput", ins_, a_.str());
}

inline std::vector<PackedTensor> LogisticRegressionOutput(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& label,
    double grad_scale = 1.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(label);
  detail::JsonBuilder a_;
  a_.put_num("grad_scale", grad_scale);
  return rt.invoke("LogisticRegressionOutput", ins_, a_.str());
}

inline std::vector<PackedTensor> MAERegressionOutput(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& label,
    double grad_scale = 1.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(label);
  detail::JsonBuilder a_;
  a_.put_num("grad_scale", grad_scale);
  return rt.invoke("MAERegressionOutput", ins_, a_.str());
}

inline std::vector<PackedTensor> MakeLoss(
    PyRuntime& rt,
    const PackedTensor& data,
    double grad_scale = 1.0,
    double valid_thresh = 0.0,
    const std::string& normalization = "null") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  a_.put_num("grad_scale", grad_scale);
  a_.put_num("valid_thresh", valid_thresh);
  a_.put_str("normalization", normalization);
  return rt.invoke("MakeLoss", ins_, a_.str());
}

inline std::vector<PackedTensor> Pad(
    PyRuntime& rt,
    const PackedTensor& data,
    const std::string& mode = "constant",
    const char* pad_width_json = nullptr,
    double constant_value = 0.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  a_.put_str("mode", mode);
  if (pad_width_json) a_.raw("pad_width", pad_width_json);
  a_.put_num("constant_value", constant_value);
  return rt.invoke("Pad", ins_, a_.str());
}

inline std::vector<PackedTensor> Pooling(
    PyRuntime& rt,
    const PackedTensor& data,
    const std::vector<long long>& kernel = {2, 2},
    const std::string& pool_type = "max",
    const char* stride_json = nullptr,
    const char* pad_json = nullptr,
    bool global_pool = false,
    const std::string& pooling_convention = "valid",
    bool count_include_pad = true,
    const char* cudnn_off_json = nullptr,
    const char* p_value_json = nullptr,
    const char* layout_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  a_.put_ivec("kernel", kernel);
  a_.put_str("pool_type", pool_type);
  if (stride_json) a_.raw("stride", stride_json);
  if (pad_json) a_.raw("pad", pad_json);
  a_.put_bool("global_pool", global_pool);
  a_.put_str("pooling_convention", pooling_convention);
  a_.put_bool("count_include_pad", count_include_pad);
  if (cudnn_off_json) a_.raw("cudnn_off", cudnn_off_json);
  if (p_value_json) a_.raw("p_value", p_value_json);
  if (layout_json) a_.raw("layout", layout_json);
  return rt.invoke("Pooling", ins_, a_.str());
}

inline std::vector<PackedTensor> RNN(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& parameters,
    const PackedTensor& state,
    const PackedTensor& state_size,
    const PackedTensor& num_layers,
    const PackedTensor* state_cell = nullptr,
    const std::string& mode = "lstm",
    bool bidirectional = false,
    double p = 0.0,
    bool state_outputs = false,
    const char* projection_size_json = nullptr,
    const char* lstm_state_clip_min_json = nullptr,
    const char* lstm_state_clip_max_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(parameters);
  ins_.push_back(state);
  ins_.push_back(state_size);
  ins_.push_back(num_layers);
  if (state_cell) ins_.push_back(*state_cell);
  detail::JsonBuilder a_;
  a_.put_str("mode", mode);
  a_.put_bool("bidirectional", bidirectional);
  a_.put_num("p", p);
  a_.put_bool("state_outputs", state_outputs);
  if (projection_size_json) a_.raw("projection_size", projection_size_json);
  if (lstm_state_clip_min_json) a_.raw("lstm_state_clip_min", lstm_state_clip_min_json);
  if (lstm_state_clip_max_json) a_.raw("lstm_state_clip_max", lstm_state_clip_max_json);
  return rt.invoke("RNN", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> ROIPooling(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& rois,
    const PackedTensor& pooled_size,
    const PackedTensor& spatial_scale) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(rois);
  ins_.push_back(pooled_size);
  ins_.push_back(spatial_scale);
  detail::JsonBuilder a_;
  return rt.invoke("ROIPooling", ins_, a_.str());
}

inline std::vector<PackedTensor> Reshape(
    PyRuntime& rt,
    const PackedTensor& data,
    const char* shape_json = nullptr,
    bool reverse = false,
    const char* target_shape_json = nullptr,
    bool keep_highest = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (shape_json) a_.raw("shape", shape_json);
  a_.put_bool("reverse", reverse);
  if (target_shape_json) a_.raw("target_shape", target_shape_json);
  a_.put_bool("keep_highest", keep_highest);
  return rt.invoke("Reshape", ins_, a_.str());
}

inline std::vector<PackedTensor> SVMOutput(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& label,
    double margin = 1.0,
    double regularization_coefficient = 1.0,
    bool use_linear = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(label);
  detail::JsonBuilder a_;
  a_.put_num("margin", margin);
  a_.put_num("regularization_coefficient", regularization_coefficient);
  a_.put_bool("use_linear", use_linear);
  return rt.invoke("SVMOutput", ins_, a_.str());
}

inline std::vector<PackedTensor> SequenceLast(
    PyRuntime& rt,
    const PackedTensor& data,
    const char* sequence_length_json = nullptr,
    bool use_sequence_length = false,
    long long axis = 0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (sequence_length_json) a_.raw("sequence_length", sequence_length_json);
  a_.put_bool("use_sequence_length", use_sequence_length);
  a_.put_int("axis", axis);
  return rt.invoke("SequenceLast", ins_, a_.str());
}

inline std::vector<PackedTensor> SequenceMask(
    PyRuntime& rt,
    const PackedTensor& data,
    const char* sequence_length_json = nullptr,
    bool use_sequence_length = false,
    double value = 0.0,
    long long axis = 0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (sequence_length_json) a_.raw("sequence_length", sequence_length_json);
  a_.put_bool("use_sequence_length", use_sequence_length);
  a_.put_num("value", value);
  a_.put_int("axis", axis);
  return rt.invoke("SequenceMask", ins_, a_.str());
}

inline std::vector<PackedTensor> SequenceReverse(
    PyRuntime& rt,
    const PackedTensor& data,
    const char* sequence_length_json = nullptr,
    bool use_sequence_length = false,
    long long axis = 0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (sequence_length_json) a_.raw("sequence_length", sequence_length_json);
  a_.put_bool("use_sequence_length", use_sequence_length);
  a_.put_int("axis", axis);
  return rt.invoke("SequenceReverse", ins_, a_.str());
}

inline std::vector<PackedTensor> SliceChannel(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& num_outputs,
    long long axis = 1,
    bool squeeze_axis = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(num_outputs);
  detail::JsonBuilder a_;
  a_.put_int("axis", axis);
  a_.put_bool("squeeze_axis", squeeze_axis);
  return rt.invoke("SliceChannel", ins_, a_.str());
}

inline std::vector<PackedTensor> SoftmaxActivation(
    PyRuntime& rt,
    const PackedTensor& data,
    const std::string& mode = "instance") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  a_.put_str("mode", mode);
  return rt.invoke("SoftmaxActivation", ins_, a_.str());
}

inline std::vector<PackedTensor> SoftmaxOutput(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& label,
    double grad_scale = 1.0,
    long long ignore_label = -1,
    bool use_ignore = false,
    bool multi_output = false,
    const std::string& normalization = "null",
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(label);
  detail::JsonBuilder a_;
  a_.put_num("grad_scale", grad_scale);
  a_.put_int("ignore_label", ignore_label);
  a_.put_bool("use_ignore", use_ignore);
  a_.put_bool("multi_output", multi_output);
  a_.put_str("normalization", normalization);
  return rt.invoke("SoftmaxOutput", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> SpatialTransformer(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& loc,
    const char* target_shape_json = nullptr,
    const std::string& transform_type = "affine",
    const std::string& sampler_type = "bilinear",
    const char* cudnn_off_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(loc);
  detail::JsonBuilder a_;
  if (target_shape_json) a_.raw("target_shape", target_shape_json);
  a_.put_str("transform_type", transform_type);
  a_.put_str("sampler_type", sampler_type);
  if (cudnn_off_json) a_.raw("cudnn_off", cudnn_off_json);
  return rt.invoke("SpatialTransformer", ins_, a_.str());
}

inline std::vector<PackedTensor> SwapAxis(
    PyRuntime& rt,
    const PackedTensor& data,
    long long dim1 = 0,
    long long dim2 = 1) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  a_.put_int("dim1", dim1);
  a_.put_int("dim2", dim2);
  return rt.invoke("SwapAxis", ins_, a_.str());
}

inline std::vector<PackedTensor> UpSampling(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    long long scale = 2,
    const std::string& sample_type = "nearest",
    const char* num_args_json = nullptr,
    const char* num_filter_json = nullptr,
    const char* multi_input_mode_json = nullptr,
    const char* workspace_json = nullptr) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  a_.put_int("scale", scale);
  a_.put_str("sample_type", sample_type);
  if (num_args_json) a_.raw("num_args", num_args_json);
  if (num_filter_json) a_.raw("num_filter", num_filter_json);
  if (multi_input_mode_json) a_.raw("multi_input_mode", multi_input_mode_json);
  if (workspace_json) a_.raw("workspace", workspace_json);
  return rt.invoke("UpSampling", ins_, a_.str());
}

inline std::vector<PackedTensor> _NoGradient(
    PyRuntime& rt,
    const PackedTensor& x,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_NoGradient", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _adabelief_update(
    PyRuntime& rt,
    const PackedTensor& weight,
    const PackedTensor& grad,
    const PackedTensor& mean,
    const PackedTensor& var,
    const PackedTensor& lr,
    double beta1 = 0.9,
    double beta2 = 0.999,
    double epsilon = 1e-08,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(weight);
  ins_.push_back(grad);
  ins_.push_back(mean);
  ins_.push_back(var);
  ins_.push_back(lr);
  detail::JsonBuilder a_;
  a_.put_num("beta1", beta1);
  a_.put_num("beta2", beta2);
  a_.put_num("epsilon", epsilon);
  a_.put_num("wd", wd);
  a_.put_num("rescale_grad", rescale_grad);
  a_.put_num("clip_gradient", clip_gradient);
  return rt.invoke("_adabelief_update", ins_, a_.str());
}

inline std::vector<PackedTensor> _adamw_update(
    PyRuntime& rt,
    const PackedTensor& weight,
    const PackedTensor& grad,
    const PackedTensor& mean,
    const PackedTensor& var,
    const PackedTensor& lr,
    double beta1 = 0.9,
    double beta2 = 0.999,
    double epsilon = 1e-08,
    double wd = 0.0,
    double eta = 1.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(weight);
  ins_.push_back(grad);
  ins_.push_back(mean);
  ins_.push_back(var);
  ins_.push_back(lr);
  detail::JsonBuilder a_;
  a_.put_num("beta1", beta1);
  a_.put_num("beta2", beta2);
  a_.put_num("epsilon", epsilon);
  a_.put_num("wd", wd);
  a_.put_num("eta", eta);
  a_.put_num("rescale_grad", rescale_grad);
  a_.put_num("clip_gradient", clip_gradient);
  return rt.invoke("_adamw_update", ins_, a_.str());
}

inline std::vector<PackedTensor> _arange(
    PyRuntime& rt,
    double start = 0.0,
    const char* stop_json = nullptr,
    double step = 1.0,
    long long repeat = 1,
    const char* dtype_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  detail::JsonBuilder a_;
  a_.put_num("start", start);
  if (stop_json) a_.raw("stop", stop_json);
  a_.put_num("step", step);
  a_.put_int("repeat", repeat);
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("_arange", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _cond(
    PyRuntime& rt,
    const PackedTensor& pred,
    const PackedTensor& then_func,
    const PackedTensor& else_func,
    const std::vector<long long>& inputs = {}) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(pred);
  ins_.push_back(then_func);
  ins_.push_back(else_func);
  detail::JsonBuilder a_;
  a_.put_ivec("inputs", inputs);
  return rt.invoke("_cond", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_AdaptiveAvgPooling2D(
    PyRuntime& rt,
    const PackedTensor& data,
    long long output_size = 1) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  a_.put_int("output_size", output_size);
  return rt.invoke("_contrib_AdaptiveAvgPooling2D", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_BatchNormWithReLU(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  return rt.invoke("_contrib_BatchNormWithReLU", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _contrib_BilinearResize2D(
    PyRuntime& rt,
    const PackedTensor& data,
    const char* height_json = nullptr,
    const char* width_json = nullptr,
    const char* scale_height_json = nullptr,
    const char* scale_width_json = nullptr,
    const std::string& mode = "size") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (height_json) a_.raw("height", height_json);
  if (width_json) a_.raw("width", width_json);
  if (scale_height_json) a_.raw("scale_height", scale_height_json);
  if (scale_width_json) a_.raw("scale_width", scale_width_json);
  a_.put_str("mode", mode);
  return rt.invoke("_contrib_BilinearResize2D", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_MultiBoxDetection(
    PyRuntime& rt,
    const PackedTensor& cls_prob,
    const PackedTensor& loc_pred,
    const PackedTensor& anchors,
    bool clip = true,
    double threshold = 0.01,
    double nms_threshold = 0.5,
    bool force_suppress = false,
    const std::vector<double>& variances = {0.1, 0.1, 0.2, 0.2},
    long long nms_topk = -1,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(cls_prob);
  ins_.push_back(loc_pred);
  ins_.push_back(anchors);
  detail::JsonBuilder a_;
  a_.put_bool("clip", clip);
  a_.put_num("threshold", threshold);
  a_.put_num("nms_threshold", nms_threshold);
  a_.put_bool("force_suppress", force_suppress);
  a_.put_fvec("variances", variances);
  a_.put_int("nms_topk", nms_topk);
  return rt.invoke("_contrib_MultiBoxDetection", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _contrib_MultiBoxPrior(
    PyRuntime& rt,
    const PackedTensor& data,
    const std::vector<double>& sizes = {1.0},
    const std::vector<double>& ratios = {1.0},
    bool clip = false,
    const std::vector<double>& steps = {-1.0, -1.0},
    const std::vector<double>& offsets = {0.5, 0.5}) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  a_.put_fvec("sizes", sizes);
  a_.put_fvec("ratios", ratios);
  a_.put_bool("clip", clip);
  a_.put_fvec("steps", steps);
  a_.put_fvec("offsets", offsets);
  return rt.invoke("_contrib_MultiBoxPrior", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_MultiBoxTarget(
    PyRuntime& rt,
    const PackedTensor& anchors,
    const PackedTensor& labels,
    const PackedTensor& cls_preds,
    double overlap_threshold = 0.5,
    long long ignore_label = -1,
    long long negative_mining_ratio = -1,
    const std::vector<double>& variances = {0.1, 0.1, 0.2, 0.2},
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(anchors);
  ins_.push_back(labels);
  ins_.push_back(cls_preds);
  detail::JsonBuilder a_;
  a_.put_num("overlap_threshold", overlap_threshold);
  a_.put_int("ignore_label", ignore_label);
  a_.put_int("negative_mining_ratio", negative_mining_ratio);
  a_.put_fvec("variances", variances);
  return rt.invoke("_contrib_MultiBoxTarget", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _contrib_ROIAlign(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& rois,
    const PackedTensor& pooled_size,
    double spatial_scale = 1.0,
    long long sample_ratio = -1,
    long long max_adaptive_samples = 4) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(rois);
  ins_.push_back(pooled_size);
  detail::JsonBuilder a_;
  a_.put_num("spatial_scale", spatial_scale);
  a_.put_int("sample_ratio", sample_ratio);
  a_.put_int("max_adaptive_samples", max_adaptive_samples);
  return rt.invoke("_contrib_ROIAlign", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_RROIAlign(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& rois,
    const PackedTensor& pooled_size,
    double spatial_scale = 1.0,
    long long sampling_ratio = 2) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(rois);
  ins_.push_back(pooled_size);
  detail::JsonBuilder a_;
  a_.put_num("spatial_scale", spatial_scale);
  a_.put_int("sampling_ratio", sampling_ratio);
  return rt.invoke("_contrib_RROIAlign", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_SyncBatchNorm(
    PyRuntime& rt,
    const PackedTensor& x,
    const PackedTensor& gamma,
    const PackedTensor& beta,
    const PackedTensor& moving_mean,
    const PackedTensor& moving_var,
    double eps = 1e-05,
    double momentum = 0.9,
    bool training = true,
    bool use_global_stats = false,
    long long axis = 1) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  ins_.push_back(gamma);
  ins_.push_back(beta);
  ins_.push_back(moving_mean);
  ins_.push_back(moving_var);
  detail::JsonBuilder a_;
  a_.put_num("eps", eps);
  a_.put_num("momentum", momentum);
  a_.put_bool("training", training);
  a_.put_bool("use_global_stats", use_global_stats);
  a_.put_int("axis", axis);
  return rt.invoke("_contrib_SyncBatchNorm", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_allclose(
    PyRuntime& rt,
    const PackedTensor& a,
    const PackedTensor& b,
    double rtol = 1e-05,
    double atol = 1e-08,
    bool equal_nan = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  ins_.push_back(b);
  detail::JsonBuilder a_;
  a_.put_num("rtol", rtol);
  a_.put_num("atol", atol);
  a_.put_bool("equal_nan", equal_nan);
  return rt.invoke("_contrib_allclose", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_arange_like(
    PyRuntime& rt,
    const PackedTensor& data,
    double start = 0.0,
    double step = 1.0,
    long long repeat = 1,
    const char* axis_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  a_.put_num("start", start);
  a_.put_num("step", step);
  a_.put_int("repeat", repeat);
  if (axis_json) a_.raw("axis", axis_json);
  return rt.invoke("_contrib_arange_like", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_bipartite_matching(
    PyRuntime& rt,
    const PackedTensor& data,
    double threshold = 1e-12,
    bool is_ascend = false,
    long long topk = -1) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  a_.put_num("threshold", threshold);
  a_.put_bool("is_ascend", is_ascend);
  a_.put_int("topk", topk);
  return rt.invoke("_contrib_bipartite_matching", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_boolean_mask(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& index,
    long long axis = 0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(index);
  detail::JsonBuilder a_;
  a_.put_int("axis", axis);
  return rt.invoke("_contrib_boolean_mask", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_box_decode(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& anchors,
    double std0 = 0.1,
    double std1 = 0.1,
    double std2 = 0.2,
    double std3 = 0.2,
    double clip = -1.0,
    const std::string& format = "corner") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(anchors);
  detail::JsonBuilder a_;
  a_.put_num("std0", std0);
  a_.put_num("std1", std1);
  a_.put_num("std2", std2);
  a_.put_num("std3", std3);
  a_.put_num("clip", clip);
  a_.put_str("format", format);
  return rt.invoke("_contrib_box_decode", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_box_encode(
    PyRuntime& rt,
    const PackedTensor& samples,
    const PackedTensor& matches,
    const PackedTensor& anchors,
    const PackedTensor& refs,
    const std::vector<double>& means = {0.0, 0.0, 0.0, 0.0},
    const std::vector<double>& stds = {0.1, 0.1, 0.2, 0.2}) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(samples);
  ins_.push_back(matches);
  ins_.push_back(anchors);
  ins_.push_back(refs);
  detail::JsonBuilder a_;
  a_.put_fvec("means", means);
  a_.put_fvec("stds", stds);
  return rt.invoke("_contrib_box_encode", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_box_iou(
    PyRuntime& rt,
    const PackedTensor& lhs,
    const PackedTensor& rhs,
    const std::string& format = "corner") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(lhs);
  ins_.push_back(rhs);
  detail::JsonBuilder a_;
  a_.put_str("format", format);
  return rt.invoke("_contrib_box_iou", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_box_nms(
    PyRuntime& rt,
    const PackedTensor& data,
    double overlap_thresh = 0.5,
    long long valid_thresh = 0,
    long long topk = -1,
    long long coord_start = 2,
    long long score_index = 1,
    long long id_index = -1,
    bool force_suppress = false,
    const std::string& in_format = "corner",
    const std::string& out_format = "corner") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  a_.put_num("overlap_thresh", overlap_thresh);
  a_.put_int("valid_thresh", valid_thresh);
  a_.put_int("topk", topk);
  a_.put_int("coord_start", coord_start);
  a_.put_int("score_index", score_index);
  a_.put_int("id_index", id_index);
  a_.put_bool("force_suppress", force_suppress);
  a_.put_str("in_format", in_format);
  a_.put_str("out_format", out_format);
  return rt.invoke("_contrib_box_nms", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_calibrate_entropy(
    PyRuntime& rt,
    const PackedTensor& arr,
    long long num_bins = 2048,
    long long num_quantized_bins = 128) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(arr);
  detail::JsonBuilder a_;
  a_.put_int("num_bins", num_bins);
  a_.put_int("num_quantized_bins", num_quantized_bins);
  return rt.invoke("_contrib_calibrate_entropy", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_dequantize(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& min_range,
    const PackedTensor& max_range,
    const std::string& out_type = "float32") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(min_range);
  ins_.push_back(max_range);
  detail::JsonBuilder a_;
  a_.put_str("out_type", out_type);
  return rt.invoke("_contrib_dequantize", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_dgl_adjacency(
    PyRuntime& rt,
    const PackedTensor& data) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  return rt.invoke("_contrib_dgl_adjacency", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_dgl_csr_neighbor_non_uniform_sample(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& csr_matrix,
    const PackedTensor& probability,
    const char* num_args_json = nullptr,
    long long num_hops = 1,
    long long num_neighbor = 2,
    long long max_num_vertices = 100) {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(csr_matrix);
  ins_.push_back(probability);
  detail::JsonBuilder a_;
  if (num_args_json) a_.raw("num_args", num_args_json);
  a_.put_int("num_hops", num_hops);
  a_.put_int("num_neighbor", num_neighbor);
  a_.put_int("max_num_vertices", max_num_vertices);
  return rt.invoke("_contrib_dgl_csr_neighbor_non_uniform_sample", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_dgl_csr_neighbor_uniform_sample(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& csr_matrix,
    const char* num_args_json = nullptr,
    long long num_hops = 1,
    long long num_neighbor = 2,
    long long max_num_vertices = 100) {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(csr_matrix);
  detail::JsonBuilder a_;
  if (num_args_json) a_.raw("num_args", num_args_json);
  a_.put_int("num_hops", num_hops);
  a_.put_int("num_neighbor", num_neighbor);
  a_.put_int("max_num_vertices", max_num_vertices);
  return rt.invoke("_contrib_dgl_csr_neighbor_uniform_sample", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_dgl_graph_compact(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* graph_sizes_json = nullptr,
    bool return_mapping = false,
    const char* num_args_json = nullptr) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (graph_sizes_json) a_.raw("graph_sizes", graph_sizes_json);
  a_.put_bool("return_mapping", return_mapping);
  if (num_args_json) a_.raw("num_args", num_args_json);
  return rt.invoke("_contrib_dgl_graph_compact", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_dgl_subgraph(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& graph,
    bool return_mapping = false,
    const char* num_args_json = nullptr) {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(graph);
  detail::JsonBuilder a_;
  a_.put_bool("return_mapping", return_mapping);
  if (num_args_json) a_.raw("num_args", num_args_json);
  return rt.invoke("_contrib_dgl_subgraph", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_div_sqrt_dim(
    PyRuntime& rt,
    const PackedTensor& data) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  return rt.invoke("_contrib_div_sqrt_dim", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_dynamic_reshape(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& shape_like) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(shape_like);
  detail::JsonBuilder a_;
  return rt.invoke("_contrib_dynamic_reshape", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_edge_id(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& u,
    const PackedTensor& v) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(u);
  ins_.push_back(v);
  detail::JsonBuilder a_;
  return rt.invoke("_contrib_edge_id", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_getnnz(
    PyRuntime& rt,
    const PackedTensor& data,
    const char* axis_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  return rt.invoke("_contrib_getnnz", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_gradientmultiplier(
    PyRuntime& rt,
    const PackedTensor& data,
    double scalar = 1.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  a_.put_num("scalar", scalar);
  return rt.invoke("_contrib_gradientmultiplier", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_group_adagrad_update(
    PyRuntime& rt,
    const PackedTensor& weight,
    const PackedTensor& grad,
    const PackedTensor& history,
    const PackedTensor& lr,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    double epsilon = 1e-05) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(weight);
  ins_.push_back(grad);
  ins_.push_back(history);
  ins_.push_back(lr);
  detail::JsonBuilder a_;
  a_.put_num("rescale_grad", rescale_grad);
  a_.put_num("clip_gradient", clip_gradient);
  a_.put_num("epsilon", epsilon);
  return rt.invoke("_contrib_group_adagrad_update", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_hawkesll(
    PyRuntime& rt,
    const PackedTensor& lda,
    const PackedTensor& alpha,
    const PackedTensor& beta,
    const PackedTensor& state,
    const PackedTensor& lags,
    const PackedTensor& marks,
    const PackedTensor& valid_length,
    const PackedTensor& max_time) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(lda);
  ins_.push_back(alpha);
  ins_.push_back(beta);
  ins_.push_back(state);
  ins_.push_back(lags);
  ins_.push_back(marks);
  ins_.push_back(valid_length);
  ins_.push_back(max_time);
  detail::JsonBuilder a_;
  return rt.invoke("_contrib_hawkesll", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_index_array(
    PyRuntime& rt,
    const PackedTensor& data,
    const char* axes_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (axes_json) a_.raw("axes", axes_json);
  return rt.invoke("_contrib_index_array", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_index_copy(
    PyRuntime& rt,
    const PackedTensor& old_tensor,
    const PackedTensor& index_vector,
    const PackedTensor& new_tensor) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(old_tensor);
  ins_.push_back(index_vector);
  ins_.push_back(new_tensor);
  detail::JsonBuilder a_;
  return rt.invoke("_contrib_index_copy", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_interleaved_matmul_encdec_qk(
    PyRuntime& rt,
    const PackedTensor& queries,
    const PackedTensor& keys_values,
    const PackedTensor& heads) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(queries);
  ins_.push_back(keys_values);
  ins_.push_back(heads);
  detail::JsonBuilder a_;
  return rt.invoke("_contrib_interleaved_matmul_encdec_qk", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_interleaved_matmul_encdec_valatt(
    PyRuntime& rt,
    const PackedTensor& keys_values,
    const PackedTensor& attention,
    const PackedTensor& heads) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(keys_values);
  ins_.push_back(attention);
  ins_.push_back(heads);
  detail::JsonBuilder a_;
  return rt.invoke("_contrib_interleaved_matmul_encdec_valatt", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_interleaved_matmul_selfatt_qk(
    PyRuntime& rt,
    const PackedTensor& queries_keys_values,
    const PackedTensor& heads) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(queries_keys_values);
  ins_.push_back(heads);
  detail::JsonBuilder a_;
  return rt.invoke("_contrib_interleaved_matmul_selfatt_qk", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_interleaved_matmul_selfatt_valatt(
    PyRuntime& rt,
    const PackedTensor& queries_keys_values,
    const PackedTensor& attention,
    const PackedTensor& heads) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(queries_keys_values);
  ins_.push_back(attention);
  ins_.push_back(heads);
  detail::JsonBuilder a_;
  return rt.invoke("_contrib_interleaved_matmul_selfatt_valatt", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_mrcnn_mask_target(
    PyRuntime& rt,
    const PackedTensor& rois,
    const PackedTensor& gt_masks,
    const PackedTensor& matches,
    const PackedTensor& cls_targets,
    const char* num_rois_json = nullptr,
    long long num_classes = 2,
    const std::vector<long long>& mask_size = {14, 14},
    long long sample_ratio = 2,
    bool aligned = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(rois);
  ins_.push_back(gt_masks);
  ins_.push_back(matches);
  ins_.push_back(cls_targets);
  detail::JsonBuilder a_;
  if (num_rois_json) a_.raw("num_rois", num_rois_json);
  a_.put_int("num_classes", num_classes);
  a_.put_ivec("mask_size", mask_size);
  a_.put_int("sample_ratio", sample_ratio);
  a_.put_bool("aligned", aligned);
  return rt.invoke("_contrib_mrcnn_mask_target", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_quadratic(
    PyRuntime& rt,
    const PackedTensor& data,
    double a = 0.0,
    double b = 0.0,
    double c = 0.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  a_.put_num("a", a);
  a_.put_num("b", b);
  a_.put_num("c", c);
  return rt.invoke("_contrib_quadratic", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_quantize(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& min_range,
    const PackedTensor& max_range,
    const std::string& out_type = "int8") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(min_range);
  ins_.push_back(max_range);
  detail::JsonBuilder a_;
  a_.put_str("out_type", out_type);
  return rt.invoke("_contrib_quantize", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_quantize_v2(
    PyRuntime& rt,
    const PackedTensor& data,
    const char* min_calib_range_json = nullptr,
    const char* max_calib_range_json = nullptr,
    const std::string& out_type = "int8") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (min_calib_range_json) a_.raw("min_calib_range", min_calib_range_json);
  if (max_calib_range_json) a_.raw("max_calib_range", max_calib_range_json);
  a_.put_str("out_type", out_type);
  return rt.invoke("_contrib_quantize_v2", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_quantized_act(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& min_data,
    const PackedTensor& max_data,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(min_data);
  ins_.push_back(max_data);
  detail::JsonBuilder a_;
  return rt.invoke("_contrib_quantized_act", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _contrib_quantized_batch_norm(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& gamma,
    const PackedTensor& beta,
    const PackedTensor& moving_mean,
    const PackedTensor& moving_var,
    const PackedTensor& min_data,
    const PackedTensor& max_data,
    double eps = 0.001,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(gamma);
  ins_.push_back(beta);
  ins_.push_back(moving_mean);
  ins_.push_back(moving_var);
  ins_.push_back(min_data);
  ins_.push_back(max_data);
  detail::JsonBuilder a_;
  a_.put_num("eps", eps);
  return rt.invoke("_contrib_quantized_batch_norm", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _contrib_quantized_concat(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    long long dim = 1,
    const char* num_args_json = nullptr) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  a_.put_int("dim", dim);
  if (num_args_json) a_.raw("num_args", num_args_json);
  return rt.invoke("_contrib_quantized_concat", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_quantized_conv(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& weight,
    const PackedTensor& bias,
    const PackedTensor& min_data,
    const PackedTensor& max_data,
    const PackedTensor& min_weight,
    const PackedTensor& max_weight,
    const PackedTensor* min_bias = nullptr,
    const PackedTensor* max_bias = nullptr,
    const char* kernel_json = nullptr,
    const std::vector<long long>& stride = {1, 1},
    const std::vector<long long>& pad = {0, 0},
    const std::vector<long long>& dilate = {1, 1},
    long long num_filter = 0,
    long long num_group = 1,
    bool no_bias = false,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(weight);
  ins_.push_back(bias);
  ins_.push_back(min_data);
  ins_.push_back(max_data);
  ins_.push_back(min_weight);
  ins_.push_back(max_weight);
  if (min_bias) ins_.push_back(*min_bias);
  if (max_bias) ins_.push_back(*max_bias);
  detail::JsonBuilder a_;
  if (kernel_json) a_.raw("kernel", kernel_json);
  a_.put_ivec("stride", stride);
  a_.put_ivec("pad", pad);
  a_.put_ivec("dilate", dilate);
  a_.put_int("num_filter", num_filter);
  a_.put_int("num_group", num_group);
  a_.put_bool("no_bias", no_bias);
  return rt.invoke("_contrib_quantized_conv", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _contrib_quantized_elemwise_add(
    PyRuntime& rt,
    const PackedTensor& lhs,
    const PackedTensor& rhs,
    const PackedTensor& lhs_min,
    const PackedTensor& lhs_max,
    const PackedTensor& rhs_min,
    const PackedTensor& rhs_max) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(lhs);
  ins_.push_back(rhs);
  ins_.push_back(lhs_min);
  ins_.push_back(lhs_max);
  ins_.push_back(rhs_min);
  ins_.push_back(rhs_max);
  detail::JsonBuilder a_;
  return rt.invoke("_contrib_quantized_elemwise_add", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_quantized_elemwise_mul(
    PyRuntime& rt,
    const PackedTensor& lhs,
    const PackedTensor& rhs,
    const PackedTensor& lhs_min,
    const PackedTensor& lhs_max,
    const PackedTensor& rhs_min,
    const PackedTensor& rhs_max) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(lhs);
  ins_.push_back(rhs);
  ins_.push_back(lhs_min);
  ins_.push_back(lhs_max);
  ins_.push_back(rhs_min);
  ins_.push_back(rhs_max);
  detail::JsonBuilder a_;
  return rt.invoke("_contrib_quantized_elemwise_mul", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_quantized_embedding(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& weight,
    const PackedTensor& min_weight,
    const PackedTensor& max_weight,
    const char* input_dim_json = nullptr,
    const char* output_dim_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(weight);
  ins_.push_back(min_weight);
  ins_.push_back(max_weight);
  detail::JsonBuilder a_;
  if (input_dim_json) a_.raw("input_dim", input_dim_json);
  if (output_dim_json) a_.raw("output_dim", output_dim_json);
  return rt.invoke("_contrib_quantized_embedding", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _contrib_quantized_flatten(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& min_data,
    const PackedTensor& max_data) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(min_data);
  ins_.push_back(max_data);
  detail::JsonBuilder a_;
  return rt.invoke("_contrib_quantized_flatten", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_quantized_fully_connected(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& weight,
    const PackedTensor& bias,
    const PackedTensor& min_data,
    const PackedTensor& max_data,
    const PackedTensor& min_weight,
    const PackedTensor& max_weight,
    const PackedTensor* min_bias = nullptr,
    const PackedTensor* max_bias = nullptr,
    long long num_hidden = 0,
    bool no_bias = false,
    bool flatten = true,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(weight);
  ins_.push_back(bias);
  ins_.push_back(min_data);
  ins_.push_back(max_data);
  ins_.push_back(min_weight);
  ins_.push_back(max_weight);
  if (min_bias) ins_.push_back(*min_bias);
  if (max_bias) ins_.push_back(*max_bias);
  detail::JsonBuilder a_;
  a_.put_int("num_hidden", num_hidden);
  a_.put_bool("no_bias", no_bias);
  a_.put_bool("flatten", flatten);
  return rt.invoke("_contrib_quantized_fully_connected", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _contrib_quantized_pooling(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& min_data,
    const PackedTensor& max_data,
    const std::vector<long long>& kernel = {2, 2},
    const std::string& pool_type = "max",
    const char* stride_json = nullptr,
    const char* pad_json = nullptr,
    bool global_pool = false,
    bool ceil_mode = false,
    const char* pooling_convention_json = nullptr,
    const char* layout_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(min_data);
  ins_.push_back(max_data);
  detail::JsonBuilder a_;
  a_.put_ivec("kernel", kernel);
  a_.put_str("pool_type", pool_type);
  if (stride_json) a_.raw("stride", stride_json);
  if (pad_json) a_.raw("pad", pad_json);
  a_.put_bool("global_pool", global_pool);
  a_.put_bool("ceil_mode", ceil_mode);
  if (pooling_convention_json) a_.raw("pooling_convention", pooling_convention_json);
  if (layout_json) a_.raw("layout", layout_json);
  return rt.invoke("_contrib_quantized_pooling", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _contrib_requantize(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& min_range,
    const PackedTensor& max_range,
    const char* min_calib_range_json = nullptr,
    const char* max_calib_range_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(min_range);
  ins_.push_back(max_range);
  detail::JsonBuilder a_;
  if (min_calib_range_json) a_.raw("min_calib_range", min_calib_range_json);
  if (max_calib_range_json) a_.raw("max_calib_range", max_calib_range_json);
  return rt.invoke("_contrib_requantize", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_round_ste(
    PyRuntime& rt,
    const PackedTensor& data) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  return rt.invoke("_contrib_round_ste", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_sign_ste(
    PyRuntime& rt,
    const PackedTensor& data) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  return rt.invoke("_contrib_sign_ste", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_sldwin_atten_context(
    PyRuntime& rt,
    const PackedTensor& score,
    const PackedTensor& value,
    const PackedTensor& dilation,
    long long w = 2,
    bool symmetric = true) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(score);
  ins_.push_back(value);
  ins_.push_back(dilation);
  detail::JsonBuilder a_;
  a_.put_int("w", w);
  a_.put_bool("symmetric", symmetric);
  return rt.invoke("_contrib_sldwin_atten_context", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_sldwin_atten_mask_like(
    PyRuntime& rt,
    const PackedTensor& score,
    const PackedTensor& dilation,
    const PackedTensor& valid_length,
    const char* num_heads_json = nullptr,
    long long w = 2,
    bool symmetric = true) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(score);
  ins_.push_back(dilation);
  ins_.push_back(valid_length);
  detail::JsonBuilder a_;
  if (num_heads_json) a_.raw("num_heads", num_heads_json);
  a_.put_int("w", w);
  a_.put_bool("symmetric", symmetric);
  return rt.invoke("_contrib_sldwin_atten_mask_like", ins_, a_.str());
}

inline std::vector<PackedTensor> _contrib_sldwin_atten_score(
    PyRuntime& rt,
    const PackedTensor& query,
    const PackedTensor& key,
    const PackedTensor& dilation,
    long long w = 2,
    bool symmetric = true) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(query);
  ins_.push_back(key);
  ins_.push_back(dilation);
  detail::JsonBuilder a_;
  a_.put_int("w", w);
  a_.put_bool("symmetric", symmetric);
  return rt.invoke("_contrib_sldwin_atten_score", ins_, a_.str());
}

inline std::vector<PackedTensor> _copy(
    PyRuntime& rt,
    const PackedTensor& a,
    const char* dtype_json = nullptr,
    const char* order_json = nullptr,
    const char* copy_json = nullptr,
    const char* device_json = nullptr,
    const char* out_sharding_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  if (dtype_json) a_.raw("dtype", dtype_json);
  if (order_json) a_.raw("order", order_json);
  if (copy_json) a_.raw("copy", copy_json);
  if (device_json) a_.raw("device", device_json);
  if (out_sharding_json) a_.raw("out_sharding", out_sharding_json);
  return rt.invoke("_copy", ins_, a_.str());
}

inline std::vector<PackedTensor> _copyto(
    PyRuntime& rt,
    const PackedTensor& x,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_copyto", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _cvcopyMakeBorder(
    PyRuntime& rt,
    const PackedTensor& src,
    const PackedTensor& top,
    const PackedTensor& bot,
    const PackedTensor& left,
    const PackedTensor& right,
    long long type = 0,
    long long values = 0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(src);
  ins_.push_back(top);
  ins_.push_back(bot);
  ins_.push_back(left);
  ins_.push_back(right);
  detail::JsonBuilder a_;
  a_.put_int("type", type);
  a_.put_int("values", values);
  return rt.invoke("_cvcopyMakeBorder", ins_, a_.str());
}

inline std::vector<PackedTensor> _cvimdecode(
    PyRuntime& rt,
    const PackedTensor& buf,
    long long flag = 1,
    bool to_rgb = true,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(buf);
  detail::JsonBuilder a_;
  a_.put_int("flag", flag);
  a_.put_bool("to_rgb", to_rgb);
  return rt.invoke("_cvimdecode", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _cvimread(
    PyRuntime& rt,
    const PackedTensor& filename,
    long long flag = 1,
    bool to_rgb = true,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(filename);
  detail::JsonBuilder a_;
  a_.put_int("flag", flag);
  a_.put_bool("to_rgb", to_rgb);
  return rt.invoke("_cvimread", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _cvimresize(
    PyRuntime& rt,
    const PackedTensor& src,
    const PackedTensor& w,
    const PackedTensor& h,
    long long interp = 1) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(src);
  ins_.push_back(w);
  ins_.push_back(h);
  detail::JsonBuilder a_;
  a_.put_int("interp", interp);
  return rt.invoke("_cvimresize", ins_, a_.str());
}

inline std::vector<PackedTensor> _div_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_div_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _equal(
    PyRuntime& rt,
    const PackedTensor& x,
    const PackedTensor& y) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  ins_.push_back(y);
  detail::JsonBuilder a_;
  return rt.invoke("_equal", ins_, a_.str());
}

inline std::vector<PackedTensor> _equal_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_equal_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _eye(
    PyRuntime& rt,
    const PackedTensor& N,
    const char* M_json = nullptr,
    long long k = 0,
    const char* dtype_json = nullptr,
    const char* device_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(N);
  detail::JsonBuilder a_;
  if (M_json) a_.raw("M", M_json);
  a_.put_int("k", k);
  if (dtype_json) a_.raw("dtype", dtype_json);
  if (device_json) a_.raw("device", device_json);
  return rt.invoke("_eye", ins_, a_.str());
}

inline std::vector<PackedTensor> _foreach(
    PyRuntime& rt,
    const PackedTensor& body,
    const PackedTensor& data,
    const PackedTensor& init_states) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(body);
  ins_.push_back(data);
  ins_.push_back(init_states);
  detail::JsonBuilder a_;
  return rt.invoke("_foreach", ins_, a_.str());
}

inline std::vector<PackedTensor> _full(
    PyRuntime& rt,
    const PackedTensor& shape,
    double value = 0.0,
    const char* dtype_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(shape);
  detail::JsonBuilder a_;
  a_.put_num("value", value);
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("_full", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _grad_add(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* out_json = nullptr,
    const char* where_json = nullptr) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (out_json) a_.raw("out", out_json);
  if (where_json) a_.raw("where", where_json);
  return rt.invoke("_grad_add", ins_, a_.str());
}

inline std::vector<PackedTensor> _greater(
    PyRuntime& rt,
    const PackedTensor& x,
    const PackedTensor& y) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  ins_.push_back(y);
  detail::JsonBuilder a_;
  return rt.invoke("_greater", ins_, a_.str());
}

inline std::vector<PackedTensor> _greater_equal(
    PyRuntime& rt,
    const PackedTensor& x,
    const PackedTensor& y) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  ins_.push_back(y);
  detail::JsonBuilder a_;
  return rt.invoke("_greater_equal", ins_, a_.str());
}

inline std::vector<PackedTensor> _greater_equal_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_greater_equal_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _greater_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_greater_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _histogram(
    PyRuntime& rt,
    const PackedTensor& a,
    long long bins = 10,
    const char* range_json = nullptr,
    const char* weights_json = nullptr,
    const char* density_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  a_.put_int("bins", bins);
  if (range_json) a_.raw("range", range_json);
  if (weights_json) a_.raw("weights", weights_json);
  if (density_json) a_.raw("density", density_json);
  return rt.invoke("_histogram", ins_, a_.str());
}

inline std::vector<PackedTensor> _hypot(
    PyRuntime& rt,
    const PackedTensor& x1,
    const PackedTensor& x2) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x1);
  ins_.push_back(x2);
  detail::JsonBuilder a_;
  return rt.invoke("_hypot", ins_, a_.str());
}

inline std::vector<PackedTensor> _hypot_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_hypot_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _identity_with_attr_like_rhs(
    PyRuntime& rt,
    const PackedTensor& lhs,
    const PackedTensor& rhs) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(lhs);
  ins_.push_back(rhs);
  detail::JsonBuilder a_;
  return rt.invoke("_identity_with_attr_like_rhs", ins_, a_.str());
}

inline std::vector<PackedTensor> _image_crop(
    PyRuntime& rt,
    const PackedTensor& src,
    const PackedTensor& x0,
    const PackedTensor& y0,
    const PackedTensor& w,
    const PackedTensor& h,
    const char* size_json = nullptr,
    long long interp = 2) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(src);
  ins_.push_back(x0);
  ins_.push_back(y0);
  ins_.push_back(w);
  ins_.push_back(h);
  detail::JsonBuilder a_;
  if (size_json) a_.raw("size", size_json);
  a_.put_int("interp", interp);
  return rt.invoke("_image_crop", ins_, a_.str());
}

inline std::vector<PackedTensor> _image_normalize(
    PyRuntime& rt,
    const PackedTensor& x,
    const PackedTensor& mean,
    const PackedTensor& std) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  ins_.push_back(mean);
  ins_.push_back(std);
  detail::JsonBuilder a_;
  return rt.invoke("_image_normalize", ins_, a_.str());
}

inline std::vector<PackedTensor> _image_random_crop(
    PyRuntime& rt,
    const PackedTensor& src,
    const PackedTensor& size,
    long long interp = 2) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(src);
  ins_.push_back(size);
  detail::JsonBuilder a_;
  a_.put_int("interp", interp);
  return rt.invoke("_image_random_crop", ins_, a_.str());
}

inline std::vector<PackedTensor> _image_random_resized_crop(
    PyRuntime& rt,
    const PackedTensor& src,
    const PackedTensor& size,
    const PackedTensor& area,
    const PackedTensor& ratio,
    long long interp = 2,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(src);
  ins_.push_back(size);
  ins_.push_back(area);
  ins_.push_back(ratio);
  detail::JsonBuilder a_;
  a_.put_int("interp", interp);
  return rt.invoke("_image_random_resized_crop", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _image_resize(
    PyRuntime& rt,
    const PackedTensor& src,
    const PackedTensor& w,
    const PackedTensor& h,
    long long interp = 1) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(src);
  ins_.push_back(w);
  ins_.push_back(h);
  detail::JsonBuilder a_;
  a_.put_int("interp", interp);
  return rt.invoke("_image_resize", ins_, a_.str());
}

inline std::vector<PackedTensor> _image_to_tensor(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_image_to_tensor", ins_, a_.str());
}

inline std::vector<PackedTensor> _lesser(
    PyRuntime& rt,
    const PackedTensor& x,
    const PackedTensor& y) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  ins_.push_back(y);
  detail::JsonBuilder a_;
  return rt.invoke("_lesser", ins_, a_.str());
}

inline std::vector<PackedTensor> _lesser_equal(
    PyRuntime& rt,
    const PackedTensor& x,
    const PackedTensor& y) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  ins_.push_back(y);
  detail::JsonBuilder a_;
  return rt.invoke("_lesser_equal", ins_, a_.str());
}

inline std::vector<PackedTensor> _lesser_equal_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_lesser_equal_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _lesser_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_lesser_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _linalg_cholesky(
    PyRuntime& rt,
    const PackedTensor& A,
    bool lower = true) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  a_.put_bool("lower", lower);
  return rt.invoke("_linalg_cholesky", ins_, a_.str());
}

inline std::vector<PackedTensor> _linalg_det(
    PyRuntime& rt,
    const PackedTensor& A) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  return rt.invoke("_linalg_det", ins_, a_.str());
}

inline std::vector<PackedTensor> _linalg_eig(
    PyRuntime& rt,
    const PackedTensor& A) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  return rt.invoke("_linalg_eig", ins_, a_.str());
}

inline std::vector<PackedTensor> _linalg_eigh(
    PyRuntime& rt,
    const PackedTensor& A,
    bool upper = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  a_.put_bool("upper", upper);
  return rt.invoke("_linalg_eigh", ins_, a_.str());
}

inline std::vector<PackedTensor> _linalg_eigvals(
    PyRuntime& rt,
    const PackedTensor& A) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  return rt.invoke("_linalg_eigvals", ins_, a_.str());
}

inline std::vector<PackedTensor> _linalg_eigvalsh(
    PyRuntime& rt,
    const PackedTensor& A) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  return rt.invoke("_linalg_eigvalsh", ins_, a_.str());
}

inline std::vector<PackedTensor> _linalg_extractdiag(
    PyRuntime& rt,
    const PackedTensor& A,
    long long offset = 0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  a_.put_int("offset", offset);
  return rt.invoke("_linalg_extractdiag", ins_, a_.str());
}

inline std::vector<PackedTensor> _linalg_extracttrian(
    PyRuntime& rt,
    const PackedTensor& A,
    long long offset = 0,
    bool lower = true) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  a_.put_int("offset", offset);
  a_.put_bool("lower", lower);
  return rt.invoke("_linalg_extracttrian", ins_, a_.str());
}

inline std::vector<PackedTensor> _linalg_gelqf(
    PyRuntime& rt,
    const PackedTensor& A) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  return rt.invoke("_linalg_gelqf", ins_, a_.str());
}

inline std::vector<PackedTensor> _linalg_gemm(
    PyRuntime& rt,
    const PackedTensor& A,
    const PackedTensor& B,
    const PackedTensor& C,
    bool transpose_a = false,
    bool transpose_b = false,
    double alpha = 1.0,
    double beta = 1.0,
    long long axis = -2) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  ins_.push_back(B);
  ins_.push_back(C);
  detail::JsonBuilder a_;
  a_.put_bool("transpose_a", transpose_a);
  a_.put_bool("transpose_b", transpose_b);
  a_.put_num("alpha", alpha);
  a_.put_num("beta", beta);
  a_.put_int("axis", axis);
  return rt.invoke("_linalg_gemm", ins_, a_.str());
}

inline std::vector<PackedTensor> _linalg_gemm2(
    PyRuntime& rt,
    const PackedTensor& A,
    const PackedTensor& B,
    bool transpose_a = false,
    bool transpose_b = false,
    double alpha = 1.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  ins_.push_back(B);
  detail::JsonBuilder a_;
  a_.put_bool("transpose_a", transpose_a);
  a_.put_bool("transpose_b", transpose_b);
  a_.put_num("alpha", alpha);
  return rt.invoke("_linalg_gemm2", ins_, a_.str());
}

inline std::vector<PackedTensor> _linalg_inverse(
    PyRuntime& rt,
    const PackedTensor& A) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  return rt.invoke("_linalg_inverse", ins_, a_.str());
}

inline std::vector<PackedTensor> _linalg_kron(
    PyRuntime& rt,
    const PackedTensor& a,
    const PackedTensor& b) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  ins_.push_back(b);
  detail::JsonBuilder a_;
  return rt.invoke("_linalg_kron", ins_, a_.str());
}

inline std::vector<PackedTensor> _linalg_lstsq(
    PyRuntime& rt,
    const PackedTensor& A,
    const PackedTensor& B,
    const char* rcond_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  ins_.push_back(B);
  detail::JsonBuilder a_;
  if (rcond_json) a_.raw("rcond", rcond_json);
  return rt.invoke("_linalg_lstsq", ins_, a_.str());
}

inline std::vector<PackedTensor> _linalg_makediag(
    PyRuntime& rt,
    const PackedTensor& A,
    long long offset = 0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  a_.put_int("offset", offset);
  return rt.invoke("_linalg_makediag", ins_, a_.str());
}

inline std::vector<PackedTensor> _linalg_maketrian(
    PyRuntime& rt,
    const PackedTensor& A,
    long long offset = 0,
    bool lower = true) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  a_.put_int("offset", offset);
  a_.put_bool("lower", lower);
  return rt.invoke("_linalg_maketrian", ins_, a_.str());
}

inline std::vector<PackedTensor> _linalg_matmul(
    PyRuntime& rt,
    const PackedTensor& a,
    const PackedTensor& b) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  ins_.push_back(b);
  detail::JsonBuilder a_;
  return rt.invoke("_linalg_matmul", ins_, a_.str());
}

inline std::vector<PackedTensor> _linalg_matrix_power(
    PyRuntime& rt,
    const PackedTensor& A,
    const PackedTensor& n) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  ins_.push_back(n);
  detail::JsonBuilder a_;
  return rt.invoke("_linalg_matrix_power", ins_, a_.str());
}

inline std::vector<PackedTensor> _linalg_matrix_rank(
    PyRuntime& rt,
    const PackedTensor& A,
    const char* tol_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  if (tol_json) a_.raw("tol", tol_json);
  return rt.invoke("_linalg_matrix_rank", ins_, a_.str());
}

inline std::vector<PackedTensor> _linalg_multi_dot(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  return rt.invoke("_linalg_multi_dot", ins_, a_.str());
}

inline std::vector<PackedTensor> _linalg_norm(
    PyRuntime& rt,
    const PackedTensor& A,
    const char* ord_json = nullptr,
    const char* axis_json = nullptr,
    bool keepdims = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  if (ord_json) a_.raw("ord", ord_json);
  if (axis_json) a_.raw("axis", axis_json);
  a_.put_bool("keepdims", keepdims);
  return rt.invoke("_linalg_norm", ins_, a_.str());
}

inline std::vector<PackedTensor> _linalg_pinv(
    PyRuntime& rt,
    const PackedTensor& A,
    const char* rcond_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  if (rcond_json) a_.raw("rcond", rcond_json);
  return rt.invoke("_linalg_pinv", ins_, a_.str());
}

inline std::vector<PackedTensor> _linalg_potrf(
    PyRuntime& rt,
    const PackedTensor& A) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  return rt.invoke("_linalg_potrf", ins_, a_.str());
}

inline std::vector<PackedTensor> _linalg_potri(
    PyRuntime& rt,
    const PackedTensor& A) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  return rt.invoke("_linalg_potri", ins_, a_.str());
}

inline std::vector<PackedTensor> _linalg_qr(
    PyRuntime& rt,
    const PackedTensor& A) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  return rt.invoke("_linalg_qr", ins_, a_.str());
}

inline std::vector<PackedTensor> _linalg_slogdet(
    PyRuntime& rt,
    const PackedTensor& A) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  return rt.invoke("_linalg_slogdet", ins_, a_.str());
}

inline std::vector<PackedTensor> _linalg_solve(
    PyRuntime& rt,
    const PackedTensor& A,
    const PackedTensor& B) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  ins_.push_back(B);
  detail::JsonBuilder a_;
  return rt.invoke("_linalg_solve", ins_, a_.str());
}

inline std::vector<PackedTensor> _linalg_sumlogdiag(
    PyRuntime& rt,
    const PackedTensor& A) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  return rt.invoke("_linalg_sumlogdiag", ins_, a_.str());
}

inline std::vector<PackedTensor> _linalg_svd(
    PyRuntime& rt,
    const PackedTensor& A) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  return rt.invoke("_linalg_svd", ins_, a_.str());
}

inline std::vector<PackedTensor> _linalg_syevd(
    PyRuntime& rt,
    const PackedTensor& A) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  return rt.invoke("_linalg_syevd", ins_, a_.str());
}

inline std::vector<PackedTensor> _linalg_syrk(
    PyRuntime& rt,
    const PackedTensor& A,
    bool transpose = false,
    double alpha = 1.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  a_.put_bool("transpose", transpose);
  a_.put_num("alpha", alpha);
  return rt.invoke("_linalg_syrk", ins_, a_.str());
}

inline std::vector<PackedTensor> _linalg_tensorinv(
    PyRuntime& rt,
    const PackedTensor& A,
    long long ind = 2) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  a_.put_int("ind", ind);
  return rt.invoke("_linalg_tensorinv", ins_, a_.str());
}

inline std::vector<PackedTensor> _linalg_tensorsolve(
    PyRuntime& rt,
    const PackedTensor& A,
    const PackedTensor& B,
    const char* axes_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  ins_.push_back(B);
  detail::JsonBuilder a_;
  if (axes_json) a_.raw("axes", axes_json);
  return rt.invoke("_linalg_tensorsolve", ins_, a_.str());
}

inline std::vector<PackedTensor> _linalg_trmm(
    PyRuntime& rt,
    const PackedTensor& A,
    const PackedTensor& B,
    bool transpose = false,
    bool rightside = false,
    bool lower = true,
    double alpha = 1.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  ins_.push_back(B);
  detail::JsonBuilder a_;
  a_.put_bool("transpose", transpose);
  a_.put_bool("rightside", rightside);
  a_.put_bool("lower", lower);
  a_.put_num("alpha", alpha);
  return rt.invoke("_linalg_trmm", ins_, a_.str());
}

inline std::vector<PackedTensor> _linalg_trsm(
    PyRuntime& rt,
    const PackedTensor& A,
    const PackedTensor& B,
    bool transpose = false,
    bool rightside = false,
    bool lower = true,
    double alpha = 1.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  ins_.push_back(B);
  detail::JsonBuilder a_;
  a_.put_bool("transpose", transpose);
  a_.put_bool("rightside", rightside);
  a_.put_bool("lower", lower);
  a_.put_num("alpha", alpha);
  return rt.invoke("_linalg_trsm", ins_, a_.str());
}

inline std::vector<PackedTensor> _linspace(
    PyRuntime& rt,
    double start = 0.0,
    double stop = 1.0,
    long long num = 50,
    bool endpoint = true,
    const char* dtype_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  detail::JsonBuilder a_;
  a_.put_num("start", start);
  a_.put_num("stop", stop);
  a_.put_int("num", num);
  a_.put_bool("endpoint", endpoint);
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("_linspace", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _logical_and(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* out_json = nullptr,
    const char* where_json = nullptr) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (out_json) a_.raw("out", out_json);
  if (where_json) a_.raw("where", where_json);
  return rt.invoke("_logical_and", ins_, a_.str());
}

inline std::vector<PackedTensor> _logical_and_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_logical_and_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _logical_or(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* out_json = nullptr,
    const char* where_json = nullptr) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (out_json) a_.raw("out", out_json);
  if (where_json) a_.raw("where", where_json);
  return rt.invoke("_logical_or", ins_, a_.str());
}

inline std::vector<PackedTensor> _logical_or_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_logical_or_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _logical_xor(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* out_json = nullptr,
    const char* where_json = nullptr) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (out_json) a_.raw("out", out_json);
  if (where_json) a_.raw("where", where_json);
  return rt.invoke("_logical_xor", ins_, a_.str());
}

inline std::vector<PackedTensor> _logical_xor_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_logical_xor_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _maximum(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* out_json = nullptr,
    const char* where_json = nullptr) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (out_json) a_.raw("out", out_json);
  if (where_json) a_.raw("where", where_json);
  return rt.invoke("_maximum", ins_, a_.str());
}

inline std::vector<PackedTensor> _maximum_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_maximum_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _minimum(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* out_json = nullptr,
    const char* where_json = nullptr) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (out_json) a_.raw("out", out_json);
  if (where_json) a_.raw("where", where_json);
  return rt.invoke("_minimum", ins_, a_.str());
}

inline std::vector<PackedTensor> _minimum_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_minimum_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _minus_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_minus_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _mod(
    PyRuntime& rt,
    const PackedTensor& x1,
    const PackedTensor& x2) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x1);
  ins_.push_back(x2);
  detail::JsonBuilder a_;
  return rt.invoke("_mod", ins_, a_.str());
}

inline std::vector<PackedTensor> _mod_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_mod_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _mp_adabelief_update(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& weight,
    const PackedTensor& grad,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(weight);
  ins_.push_back(grad);
  detail::JsonBuilder a_;
  return rt.invoke("_mp_adabelief_update", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _mp_adamw_update(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& weight,
    const PackedTensor& grad,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(weight);
  ins_.push_back(grad);
  detail::JsonBuilder a_;
  return rt.invoke("_mp_adamw_update", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _mul_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_mul_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _multi_adabelief_update(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* num_weights_json = nullptr,
    const char* lrs_json = nullptr,
    const char* wds_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (num_weights_json) a_.raw("num_weights", num_weights_json);
  if (lrs_json) a_.raw("lrs", lrs_json);
  if (wds_json) a_.raw("wds", wds_json);
  return rt.invoke("_multi_adabelief_update", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _multi_adamw_update(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* num_weights_json = nullptr,
    const char* lrs_json = nullptr,
    const char* wds_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (num_weights_json) a_.raw("num_weights", num_weights_json);
  if (lrs_json) a_.raw("lrs", lrs_json);
  if (wds_json) a_.raw("wds", wds_json);
  return rt.invoke("_multi_adamw_update", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _multi_lamb_update(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* num_weights_json = nullptr,
    const char* lrs_json = nullptr,
    const char* wds_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (num_weights_json) a_.raw("num_weights", num_weights_json);
  if (lrs_json) a_.raw("lrs", lrs_json);
  if (wds_json) a_.raw("wds", wds_json);
  return rt.invoke("_multi_lamb_update", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _multi_lans_update(
    PyRuntime& rt,
    const PackedTensor& weight,
    const PackedTensor& grad,
    const PackedTensor& mean,
    const PackedTensor& var,
    double beta1 = 0.9,
    double beta2 = 0.999,
    double epsilon = 1e-06,
    long long t = 1,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(weight);
  ins_.push_back(grad);
  ins_.push_back(mean);
  ins_.push_back(var);
  detail::JsonBuilder a_;
  a_.put_num("beta1", beta1);
  a_.put_num("beta2", beta2);
  a_.put_num("epsilon", epsilon);
  a_.put_int("t", t);
  a_.put_num("wd", wd);
  a_.put_num("rescale_grad", rescale_grad);
  a_.put_num("clip_gradient", clip_gradient);
  return rt.invoke("_multi_lans_update", ins_, a_.str());
}

inline std::vector<PackedTensor> _multi_mp_adabelief_update(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* num_weights_json = nullptr,
    const char* lrs_json = nullptr,
    const char* wds_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (num_weights_json) a_.raw("num_weights", num_weights_json);
  if (lrs_json) a_.raw("lrs", lrs_json);
  if (wds_json) a_.raw("wds", wds_json);
  return rt.invoke("_multi_mp_adabelief_update", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _multi_mp_adamw_update(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* num_weights_json = nullptr,
    const char* lrs_json = nullptr,
    const char* wds_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (num_weights_json) a_.raw("num_weights", num_weights_json);
  if (lrs_json) a_.raw("lrs", lrs_json);
  if (wds_json) a_.raw("wds", wds_json);
  return rt.invoke("_multi_mp_adamw_update", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _multi_mp_lamb_update(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* num_weights_json = nullptr,
    const char* lrs_json = nullptr,
    const char* wds_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (num_weights_json) a_.raw("num_weights", num_weights_json);
  if (lrs_json) a_.raw("lrs", lrs_json);
  if (wds_json) a_.raw("wds", wds_json);
  return rt.invoke("_multi_mp_lamb_update", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _multi_mp_lans_update(
    PyRuntime& rt,
    const PackedTensor& weight,
    const PackedTensor& grad,
    const PackedTensor& mean,
    const PackedTensor& var,
    double beta1 = 0.9,
    double beta2 = 0.999,
    double epsilon = 1e-06,
    long long t = 1,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(weight);
  ins_.push_back(grad);
  ins_.push_back(mean);
  ins_.push_back(var);
  detail::JsonBuilder a_;
  a_.put_num("beta1", beta1);
  a_.put_num("beta2", beta2);
  a_.put_num("epsilon", epsilon);
  a_.put_int("t", t);
  a_.put_num("wd", wd);
  a_.put_num("rescale_grad", rescale_grad);
  a_.put_num("clip_gradient", clip_gradient);
  return rt.invoke("_multi_mp_lans_update", ins_, a_.str());
}

inline std::vector<PackedTensor> _not_equal(
    PyRuntime& rt,
    const PackedTensor& x,
    const PackedTensor& y) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  ins_.push_back(y);
  detail::JsonBuilder a_;
  return rt.invoke("_not_equal", ins_, a_.str());
}

inline std::vector<PackedTensor> _not_equal_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_not_equal_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _np_reshape(
    PyRuntime& rt,
    const PackedTensor& x,
    const PackedTensor& newshape,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  ins_.push_back(newshape);
  detail::JsonBuilder a_;
  return rt.invoke("_np_reshape", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_absolute(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_absolute", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_add(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* out_json = nullptr,
    const char* where_json = nullptr) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (out_json) a_.raw("out", out_json);
  if (where_json) a_.raw("where", where_json);
  return rt.invoke("_npi_add", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_add_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_add_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_advanced_indexing(
    PyRuntime& rt,
    const PackedTensor& x,
    const PackedTensor& idx) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  ins_.push_back(idx);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_advanced_indexing", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_advanced_indexing_multiple(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_advanced_indexing_multiple", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_all(
    PyRuntime& rt,
    const PackedTensor& a,
    const char* axis_json = nullptr,
    const char* out_json = nullptr,
    bool keepdims = false,
    const char* where_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  if (out_json) a_.raw("out", out_json);
  a_.put_bool("keepdims", keepdims);
  if (where_json) a_.raw("where", where_json);
  return rt.invoke("_npi_all", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_any(
    PyRuntime& rt,
    const PackedTensor& a,
    const char* axis_json = nullptr,
    const char* out_json = nullptr,
    bool keepdims = false,
    const char* where_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  if (out_json) a_.raw("out", out_json);
  a_.put_bool("keepdims", keepdims);
  if (where_json) a_.raw("where", where_json);
  return rt.invoke("_npi_any", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_arange(
    PyRuntime& rt,
    const PackedTensor& start,
    const char* stop_json = nullptr,
    long long step = 1,
    const char* dtype_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(start);
  detail::JsonBuilder a_;
  if (stop_json) a_.raw("stop", stop_json);
  a_.put_int("step", step);
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("_npi_arange", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_arccos(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_arccos", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_arccosh(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_arccosh", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_arcsin(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_arcsin", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_arcsinh(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_arcsinh", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_arctan(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_arctan", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_arctan2(
    PyRuntime& rt,
    const PackedTensor& x1,
    const PackedTensor& x2) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x1);
  ins_.push_back(x2);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_arctan2", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_arctan2_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_arctan2_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_arctanh(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_arctanh", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_argmax(
    PyRuntime& rt,
    const PackedTensor& a,
    const char* axis_json = nullptr,
    const char* out_json = nullptr,
    const char* keepdims_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  if (out_json) a_.raw("out", out_json);
  if (keepdims_json) a_.raw("keepdims", keepdims_json);
  return rt.invoke("_npi_argmax", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_argmin(
    PyRuntime& rt,
    const PackedTensor& a,
    const char* axis_json = nullptr,
    const char* out_json = nullptr,
    const char* keepdims_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  if (out_json) a_.raw("out", out_json);
  if (keepdims_json) a_.raw("keepdims", keepdims_json);
  return rt.invoke("_npi_argmin", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_around(
    PyRuntime& rt,
    const PackedTensor& a,
    long long decimals = 0,
    const char* out_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  a_.put_int("decimals", decimals);
  if (out_json) a_.raw("out", out_json);
  return rt.invoke("_npi_around", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_atleast_1d(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_atleast_1d", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_atleast_2d(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_atleast_2d", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_atleast_3d(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_atleast_3d", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_average(
    PyRuntime& rt,
    const PackedTensor& a,
    const char* axis_json = nullptr,
    const char* weights_json = nullptr,
    bool returned = false,
    bool keepdims = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  if (weights_json) a_.raw("weights", weights_json);
  a_.put_bool("returned", returned);
  a_.put_bool("keepdims", keepdims);
  return rt.invoke("_npi_average", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_bernoulli(
    PyRuntime& rt,
    const char* prob_json = nullptr,
    const char* logit_json = nullptr,
    const char* size_json = nullptr,
    const char* dtype_json = nullptr) {
  std::vector<PackedTensor> ins_;
  detail::JsonBuilder a_;
  if (prob_json) a_.raw("prob", prob_json);
  if (logit_json) a_.raw("logit", logit_json);
  if (size_json) a_.raw("size", size_json);
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("_npi_bernoulli", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_bincount(
    PyRuntime& rt,
    const PackedTensor& x,
    const char* weights_json = nullptr,
    long long minlength = 0,
    const char* length_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  if (weights_json) a_.raw("weights", weights_json);
  a_.put_int("minlength", minlength);
  if (length_json) a_.raw("length", length_json);
  return rt.invoke("_npi_bincount", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_bitwise_and(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* out_json = nullptr,
    const char* where_json = nullptr) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (out_json) a_.raw("out", out_json);
  if (where_json) a_.raw("where", where_json);
  return rt.invoke("_npi_bitwise_and", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_bitwise_and_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_bitwise_and_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_bitwise_left_shift(
    PyRuntime& rt,
    const PackedTensor& x,
    const PackedTensor& y) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  ins_.push_back(y);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_bitwise_left_shift", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_bitwise_left_shift_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_bitwise_left_shift_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_bitwise_not(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_bitwise_not", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_bitwise_or(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* out_json = nullptr,
    const char* where_json = nullptr) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (out_json) a_.raw("out", out_json);
  if (where_json) a_.raw("where", where_json);
  return rt.invoke("_npi_bitwise_or", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_bitwise_or_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_bitwise_or_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_bitwise_right_shift(
    PyRuntime& rt,
    const PackedTensor& x1,
    const PackedTensor& x2) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x1);
  ins_.push_back(x2);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_bitwise_right_shift", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_bitwise_right_shift_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_bitwise_right_shift_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_bitwise_xor(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* out_json = nullptr,
    const char* where_json = nullptr) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (out_json) a_.raw("out", out_json);
  if (where_json) a_.raw("where", where_json);
  return rt.invoke("_npi_bitwise_xor", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_bitwise_xor_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_bitwise_xor_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_blackman(
    PyRuntime& rt,
    const PackedTensor& M,
    const char* dtype_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(M);
  detail::JsonBuilder a_;
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("_npi_blackman", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_boolean_mask_assign_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_boolean_mask_assign_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_boolean_mask_assign_tensor(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& mask,
    const PackedTensor& value) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(mask);
  ins_.push_back(value);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_boolean_mask_assign_tensor", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_broadcast_to(
    PyRuntime& rt,
    const PackedTensor& array,
    const PackedTensor& shape,
    const char* out_sharding_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(array);
  ins_.push_back(shape);
  detail::JsonBuilder a_;
  if (out_sharding_json) a_.raw("out_sharding", out_sharding_json);
  return rt.invoke("_npi_broadcast_to", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_cbrt(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_cbrt", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_ceil(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_ceil", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_choice(
    PyRuntime& rt,
    const PackedTensor& a,
    const char* size_json = nullptr,
    bool replace = true,
    const char* p_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  if (size_json) a_.raw("size", size_json);
  a_.put_bool("replace", replace);
  if (p_json) a_.raw("p", p_json);
  return rt.invoke("_npi_choice", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_cholesky(
    PyRuntime& rt,
    const PackedTensor& a,
    bool upper = false,
    bool symmetrize_input = true) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  a_.put_bool("upper", upper);
  a_.put_bool("symmetrize_input", symmetrize_input);
  return rt.invoke("_npi_cholesky", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_column_stack(
    PyRuntime& rt,
    const PackedTensor& tup) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(tup);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_column_stack", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_copy(
    PyRuntime& rt,
    const PackedTensor& a) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_copy", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_copysign(
    PyRuntime& rt,
    const PackedTensor& x1,
    const PackedTensor& x2) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x1);
  ins_.push_back(x2);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_copysign", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_copysign_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_copysign_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_cos(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_cos", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_cosh(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_cosh", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_cross(
    PyRuntime& rt,
    const PackedTensor& a,
    const PackedTensor& b,
    long long axisa = -1,
    long long axisb = -1,
    long long axisc = -1,
    const char* axis_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  ins_.push_back(b);
  detail::JsonBuilder a_;
  a_.put_int("axisa", axisa);
  a_.put_int("axisb", axisb);
  a_.put_int("axisc", axisc);
  if (axis_json) a_.raw("axis", axis_json);
  return rt.invoke("_npi_cross", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_cumsum(
    PyRuntime& rt,
    const PackedTensor& a,
    const char* axis_json = nullptr,
    const char* dtype_json = nullptr,
    const char* out_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  if (dtype_json) a_.raw("dtype", dtype_json);
  if (out_json) a_.raw("out", out_json);
  return rt.invoke("_npi_cumsum", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_deg2rad(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_deg2rad", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_degrees(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_degrees", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_delete(
    PyRuntime& rt,
    const PackedTensor& arr,
    const PackedTensor& obj,
    const char* axis_json = nullptr,
    bool assume_unique_indices = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(arr);
  ins_.push_back(obj);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  a_.put_bool("assume_unique_indices", assume_unique_indices);
  return rt.invoke("_npi_delete", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_diag(
    PyRuntime& rt,
    const PackedTensor& v,
    long long k = 0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(v);
  detail::JsonBuilder a_;
  a_.put_int("k", k);
  return rt.invoke("_npi_diag", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_diag_indices_from(
    PyRuntime& rt,
    const PackedTensor& a) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_diag_indices_from", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_diagflat(
    PyRuntime& rt,
    const PackedTensor& v,
    long long k = 0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(v);
  detail::JsonBuilder a_;
  a_.put_int("k", k);
  return rt.invoke("_npi_diagflat", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_diagonal(
    PyRuntime& rt,
    const PackedTensor& a,
    long long offset = 0,
    long long axis1 = 0,
    long long axis2 = 1) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  a_.put_int("offset", offset);
  a_.put_int("axis1", axis1);
  a_.put_int("axis2", axis2);
  return rt.invoke("_npi_diagonal", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_diff(
    PyRuntime& rt,
    const PackedTensor& a,
    long long n = 1,
    long long axis = -1,
    const char* prepend_json = nullptr,
    const char* append_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  a_.put_int("n", n);
  a_.put_int("axis", axis);
  if (prepend_json) a_.raw("prepend", prepend_json);
  if (append_json) a_.raw("append", append_json);
  return rt.invoke("_npi_diff", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_dot(
    PyRuntime& rt,
    const PackedTensor& a,
    const PackedTensor& b,
    const char* precision_json = nullptr,
    const char* preferred_element_type_json = nullptr,
    const char* out_sharding_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  ins_.push_back(b);
  detail::JsonBuilder a_;
  if (precision_json) a_.raw("precision", precision_json);
  if (preferred_element_type_json) a_.raw("preferred_element_type", preferred_element_type_json);
  if (out_sharding_json) a_.raw("out_sharding", out_sharding_json);
  return rt.invoke("_npi_dot", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_dsplit(
    PyRuntime& rt,
    const PackedTensor& ary,
    const PackedTensor& indices_or_sections) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(ary);
  ins_.push_back(indices_or_sections);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_dsplit", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_dstack(
    PyRuntime& rt,
    const PackedTensor& tup,
    const char* dtype_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(tup);
  detail::JsonBuilder a_;
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("_npi_dstack", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_ediff1d(
    PyRuntime& rt,
    const PackedTensor& ary,
    const char* to_end_json = nullptr,
    const char* to_begin_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(ary);
  detail::JsonBuilder a_;
  if (to_end_json) a_.raw("to_end", to_end_json);
  if (to_begin_json) a_.raw("to_begin", to_begin_json);
  return rt.invoke("_npi_ediff1d", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_eig(
    PyRuntime& rt,
    const PackedTensor& a) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_eig", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_eigh(
    PyRuntime& rt,
    const PackedTensor& a,
    bool upper = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  a_.put_bool("upper", upper);
  return rt.invoke("_npi_eigh", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_eigvals(
    PyRuntime& rt,
    const PackedTensor& a) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_eigvals", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_eigvalsh(
    PyRuntime& rt,
    const PackedTensor& a,
    bool upper = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  a_.put_bool("upper", upper);
  return rt.invoke("_npi_eigvalsh", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_einsum(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& subscripts,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(subscripts);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_einsum", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_exp(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_exp", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_expm1(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_expm1", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_exponential(
    PyRuntime& rt,
    double scale = 1.0,
    const char* size_json = nullptr,
    const char* dtype_json = nullptr) {
  std::vector<PackedTensor> ins_;
  detail::JsonBuilder a_;
  a_.put_num("scale", scale);
  if (size_json) a_.raw("size", size_json);
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("_npi_exponential", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_eye(
    PyRuntime& rt,
    const PackedTensor& N,
    const char* M_json = nullptr,
    long long k = 0,
    const char* dtype_json = nullptr,
    const char* device_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(N);
  detail::JsonBuilder a_;
  if (M_json) a_.raw("M", M_json);
  a_.put_int("k", k);
  if (dtype_json) a_.raw("dtype", dtype_json);
  if (device_json) a_.raw("device", device_json);
  return rt.invoke("_npi_eye", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_fill_diagonal(
    PyRuntime& rt,
    const PackedTensor& a,
    double val = 0.0,
    bool wrap = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  a_.put_num("val", val);
  a_.put_bool("wrap", wrap);
  return rt.invoke("_npi_fill_diagonal", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_fix(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_fix", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_flip(
    PyRuntime& rt,
    const PackedTensor& m,
    const char* axis_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(m);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  return rt.invoke("_npi_flip", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_floor(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_floor", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_floor_divide(
    PyRuntime& rt,
    const PackedTensor& x1,
    const PackedTensor& x2) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x1);
  ins_.push_back(x2);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_floor_divide", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_floor_divide_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_floor_divide_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_fmax(
    PyRuntime& rt,
    const PackedTensor& x1,
    const PackedTensor& x2) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x1);
  ins_.push_back(x2);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_fmax", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_fmax_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_fmax_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_fmin(
    PyRuntime& rt,
    const PackedTensor& x1,
    const PackedTensor& x2) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x1);
  ins_.push_back(x2);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_fmin", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_fmin_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_fmin_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_fmod(
    PyRuntime& rt,
    const PackedTensor& x1,
    const PackedTensor& x2) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x1);
  ins_.push_back(x2);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_fmod", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_fmod_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_fmod_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_full(
    PyRuntime& rt,
    const PackedTensor& shape,
    const PackedTensor& fill_value,
    const char* dtype_json = nullptr,
    const std::string& order = "C",
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(shape);
  ins_.push_back(fill_value);
  detail::JsonBuilder a_;
  if (dtype_json) a_.raw("dtype", dtype_json);
  a_.put_str("order", order);
  return rt.invoke("_npi_full", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_full_like(
    PyRuntime& rt,
    const PackedTensor& a,
    const PackedTensor& fill_value,
    const char* dtype_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  ins_.push_back(fill_value);
  detail::JsonBuilder a_;
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("_npi_full_like", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_gamma(
    PyRuntime& rt,
    const PackedTensor& shape,
    double scale = 1.0,
    const char* size_json = nullptr,
    const char* dtype_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(shape);
  detail::JsonBuilder a_;
  a_.put_num("scale", scale);
  if (size_json) a_.raw("size", size_json);
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("_npi_gamma", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_gcd(
    PyRuntime& rt,
    const PackedTensor& x1,
    const PackedTensor& x2) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x1);
  ins_.push_back(x2);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_gcd", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_gcd_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_gcd_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_geomspace(
    PyRuntime& rt,
    const PackedTensor& start,
    const PackedTensor& stop,
    long long num = 50,
    bool endpoint = true,
    const char* dtype_json = nullptr,
    long long axis = 0,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(start);
  ins_.push_back(stop);
  detail::JsonBuilder a_;
  a_.put_int("num", num);
  a_.put_bool("endpoint", endpoint);
  if (dtype_json) a_.raw("dtype", dtype_json);
  a_.put_int("axis", axis);
  return rt.invoke("_npi_geomspace", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_gumbel(
    PyRuntime& rt,
    double loc = 0.0,
    double scale = 1.0,
    const char* size_json = nullptr,
    const char* dtype_json = nullptr) {
  std::vector<PackedTensor> ins_;
  detail::JsonBuilder a_;
  a_.put_num("loc", loc);
  a_.put_num("scale", scale);
  if (size_json) a_.raw("size", size_json);
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("_npi_gumbel", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_hamming(
    PyRuntime& rt,
    const PackedTensor& M,
    const char* dtype_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(M);
  detail::JsonBuilder a_;
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("_npi_hamming", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_hanning(
    PyRuntime& rt,
    const PackedTensor& M,
    const char* dtype_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(M);
  detail::JsonBuilder a_;
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("_npi_hanning", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_hsplit(
    PyRuntime& rt,
    const PackedTensor& ary,
    const PackedTensor& indices_or_sections) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(ary);
  ins_.push_back(indices_or_sections);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_hsplit", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_hstack(
    PyRuntime& rt,
    const PackedTensor& tup,
    const char* dtype_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(tup);
  detail::JsonBuilder a_;
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("_npi_hstack", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_hypot(
    PyRuntime& rt,
    const PackedTensor& x1,
    const PackedTensor& x2) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x1);
  ins_.push_back(x2);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_hypot", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_identity(
    PyRuntime& rt,
    const PackedTensor& n,
    const char* dtype_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(n);
  detail::JsonBuilder a_;
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("_npi_identity", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_indices(
    PyRuntime& rt,
    const PackedTensor& dimensions,
    const char* dtype_json = nullptr,
    bool sparse = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(dimensions);
  detail::JsonBuilder a_;
  if (dtype_json) a_.raw("dtype", dtype_json);
  a_.put_bool("sparse", sparse);
  return rt.invoke("_npi_indices", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_insert_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_insert_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_insert_slice(
    PyRuntime& rt,
    const PackedTensor& arr,
    const PackedTensor& obj,
    const PackedTensor& values,
    const char* axis_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(arr);
  ins_.push_back(obj);
  ins_.push_back(values);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  return rt.invoke("_npi_insert_slice", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_insert_tensor(
    PyRuntime& rt,
    const PackedTensor& arr,
    const PackedTensor& obj,
    const PackedTensor& values,
    const char* axis_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(arr);
  ins_.push_back(obj);
  ins_.push_back(values);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  return rt.invoke("_npi_insert_tensor", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_interp(
    PyRuntime& rt,
    const PackedTensor& x,
    const PackedTensor& xp,
    const PackedTensor& fp,
    const char* left_json = nullptr,
    const char* right_json = nullptr,
    const char* period_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  ins_.push_back(xp);
  ins_.push_back(fp);
  detail::JsonBuilder a_;
  if (left_json) a_.raw("left", left_json);
  if (right_json) a_.raw("right", right_json);
  if (period_json) a_.raw("period", period_json);
  return rt.invoke("_npi_interp", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_kron(
    PyRuntime& rt,
    const PackedTensor& a,
    const PackedTensor& b) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  ins_.push_back(b);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_kron", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_laplace(
    PyRuntime& rt,
    double loc = 0.0,
    double scale = 1.0,
    const char* size_json = nullptr,
    const char* dtype_json = nullptr) {
  std::vector<PackedTensor> ins_;
  detail::JsonBuilder a_;
  a_.put_num("loc", loc);
  a_.put_num("scale", scale);
  if (size_json) a_.raw("size", size_json);
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("_npi_laplace", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_lcm(
    PyRuntime& rt,
    const PackedTensor& x1,
    const PackedTensor& x2) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x1);
  ins_.push_back(x2);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_lcm", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_lcm_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_lcm_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_ldexp(
    PyRuntime& rt,
    const PackedTensor& x1,
    const PackedTensor& x2) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x1);
  ins_.push_back(x2);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_ldexp", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_ldexp_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_ldexp_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_linspace(
    PyRuntime& rt,
    const PackedTensor& start,
    const PackedTensor& stop,
    long long num = 50,
    bool endpoint = true,
    bool retstep = false,
    const char* dtype_json = nullptr,
    long long axis = 0,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(start);
  ins_.push_back(stop);
  detail::JsonBuilder a_;
  a_.put_int("num", num);
  a_.put_bool("endpoint", endpoint);
  a_.put_bool("retstep", retstep);
  if (dtype_json) a_.raw("dtype", dtype_json);
  a_.put_int("axis", axis);
  return rt.invoke("_npi_linspace", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_log(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_log", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_log10(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_log10", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_log1p(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_log1p", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_log2(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_log2", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_logaddexp(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* out_json = nullptr,
    const char* where_json = nullptr) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (out_json) a_.raw("out", out_json);
  if (where_json) a_.raw("where", where_json);
  return rt.invoke("_npi_logaddexp", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_logaddexp_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_logaddexp_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_logical_and(
    PyRuntime& rt,
    const PackedTensor& lhs,
    const PackedTensor& rhs) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(lhs);
  ins_.push_back(rhs);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_logical_and", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_logical_not(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_logical_not", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_logical_or(
    PyRuntime& rt,
    const PackedTensor& lhs,
    const PackedTensor& rhs) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(lhs);
  ins_.push_back(rhs);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_logical_or", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_logical_xor(
    PyRuntime& rt,
    const PackedTensor& lhs,
    const PackedTensor& rhs) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(lhs);
  ins_.push_back(rhs);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_logical_xor", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_logistic(
    PyRuntime& rt,
    double loc = 0.0,
    double scale = 1.0,
    const char* size_json = nullptr,
    const char* dtype_json = nullptr) {
  std::vector<PackedTensor> ins_;
  detail::JsonBuilder a_;
  a_.put_num("loc", loc);
  a_.put_num("scale", scale);
  if (size_json) a_.raw("size", size_json);
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("_npi_logistic", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_logspace(
    PyRuntime& rt,
    const PackedTensor& start,
    const PackedTensor& stop,
    long long num = 50,
    bool endpoint = true,
    double base = 10.0,
    const char* dtype_json = nullptr,
    long long axis = 0,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(start);
  ins_.push_back(stop);
  detail::JsonBuilder a_;
  a_.put_int("num", num);
  a_.put_bool("endpoint", endpoint);
  a_.put_num("base", base);
  if (dtype_json) a_.raw("dtype", dtype_json);
  a_.put_int("axis", axis);
  return rt.invoke("_npi_logspace", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_lstsq(
    PyRuntime& rt,
    const PackedTensor& a,
    const PackedTensor& b,
    const std::string& rcond = "warn") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  ins_.push_back(b);
  detail::JsonBuilder a_;
  a_.put_str("rcond", rcond);
  return rt.invoke("_npi_lstsq", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_matmul(
    PyRuntime& rt,
    const PackedTensor& a,
    const PackedTensor& b,
    const char* precision_json = nullptr,
    const char* preferred_element_type_json = nullptr,
    const char* out_sharding_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  ins_.push_back(b);
  detail::JsonBuilder a_;
  if (precision_json) a_.raw("precision", precision_json);
  if (preferred_element_type_json) a_.raw("preferred_element_type", preferred_element_type_json);
  if (out_sharding_json) a_.raw("out_sharding", out_sharding_json);
  return rt.invoke("_npi_matmul", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_matrix_rank(
    PyRuntime& rt,
    const PackedTensor& M,
    const char* rtol_json = nullptr,
    bool hermitian = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(M);
  detail::JsonBuilder a_;
  if (rtol_json) a_.raw("rtol", rtol_json);
  a_.put_bool("hermitian", hermitian);
  return rt.invoke("_npi_matrix_rank", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_matrix_rank_none_tol(
    PyRuntime& rt,
    const PackedTensor& M,
    const char* rtol_json = nullptr,
    bool hermitian = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(M);
  detail::JsonBuilder a_;
  if (rtol_json) a_.raw("rtol", rtol_json);
  a_.put_bool("hermitian", hermitian);
  return rt.invoke("_npi_matrix_rank_none_tol", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_max(
    PyRuntime& rt,
    const PackedTensor& a,
    const char* axis_json = nullptr,
    const char* out_json = nullptr,
    bool keepdims = false,
    const char* initial_json = nullptr,
    const char* where_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  if (out_json) a_.raw("out", out_json);
  a_.put_bool("keepdims", keepdims);
  if (initial_json) a_.raw("initial", initial_json);
  if (where_json) a_.raw("where", where_json);
  return rt.invoke("_npi_max", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_maximum(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* out_json = nullptr,
    const char* where_json = nullptr) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (out_json) a_.raw("out", out_json);
  if (where_json) a_.raw("where", where_json);
  return rt.invoke("_npi_maximum", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_mean(
    PyRuntime& rt,
    const PackedTensor& a,
    const char* axis_json = nullptr,
    const char* dtype_json = nullptr,
    const char* out_json = nullptr,
    bool keepdims = false,
    const char* where_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  if (dtype_json) a_.raw("dtype", dtype_json);
  if (out_json) a_.raw("out", out_json);
  a_.put_bool("keepdims", keepdims);
  if (where_json) a_.raw("where", where_json);
  return rt.invoke("_npi_mean", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_min(
    PyRuntime& rt,
    const PackedTensor& a,
    const char* axis_json = nullptr,
    const char* out_json = nullptr,
    bool keepdims = false,
    const char* initial_json = nullptr,
    const char* where_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  if (out_json) a_.raw("out", out_json);
  a_.put_bool("keepdims", keepdims);
  if (initial_json) a_.raw("initial", initial_json);
  if (where_json) a_.raw("where", where_json);
  return rt.invoke("_npi_min", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_minimum(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* out_json = nullptr,
    const char* where_json = nullptr) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (out_json) a_.raw("out", out_json);
  if (where_json) a_.raw("where", where_json);
  return rt.invoke("_npi_minimum", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_mod(
    PyRuntime& rt,
    const PackedTensor& x1,
    const PackedTensor& x2) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x1);
  ins_.push_back(x2);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_mod", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_mod_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_mod_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_moveaxis(
    PyRuntime& rt,
    const PackedTensor& a,
    const PackedTensor& source,
    const PackedTensor& destination) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  ins_.push_back(source);
  ins_.push_back(destination);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_moveaxis", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_multinomial(
    PyRuntime& rt,
    const PackedTensor& n,
    const PackedTensor& pvals,
    const char* size_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(n);
  ins_.push_back(pvals);
  detail::JsonBuilder a_;
  if (size_json) a_.raw("size", size_json);
  return rt.invoke("_npi_multinomial", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_multiply(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* out_json = nullptr,
    const char* where_json = nullptr) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (out_json) a_.raw("out", out_json);
  if (where_json) a_.raw("where", where_json);
  return rt.invoke("_npi_multiply", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_multiply_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_multiply_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_nan_to_num(
    PyRuntime& rt,
    const PackedTensor& x,
    bool copy = true,
    double nan = 0.0,
    const char* posinf_json = nullptr,
    const char* neginf_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  a_.put_bool("copy", copy);
  a_.put_num("nan", nan);
  if (posinf_json) a_.raw("posinf", posinf_json);
  if (neginf_json) a_.raw("neginf", neginf_json);
  return rt.invoke("_npi_nan_to_num", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_negative(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* out_json = nullptr,
    const char* where_json = nullptr) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (out_json) a_.raw("out", out_json);
  if (where_json) a_.raw("where", where_json);
  return rt.invoke("_npi_negative", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_norm(
    PyRuntime& rt,
    const PackedTensor& x,
    const char* ord_json = nullptr,
    const char* axis_json = nullptr,
    bool keepdims = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  if (ord_json) a_.raw("ord", ord_json);
  if (axis_json) a_.raw("axis", axis_json);
  a_.put_bool("keepdims", keepdims);
  return rt.invoke("_npi_norm", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_normal(
    PyRuntime& rt,
    double loc = 0.0,
    double scale = 1.0,
    const char* size_json = nullptr,
    const char* dtype_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  detail::JsonBuilder a_;
  a_.put_num("loc", loc);
  a_.put_num("scale", scale);
  if (size_json) a_.raw("size", size_json);
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("_npi_normal", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_normal_n(
    PyRuntime& rt,
    double loc = 0.0,
    double scale = 1.0,
    const char* size_json = nullptr,
    const char* dtype_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  detail::JsonBuilder a_;
  a_.put_num("loc", loc);
  a_.put_num("scale", scale);
  if (size_json) a_.raw("size", size_json);
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("_npi_normal_n", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_ones(
    PyRuntime& rt,
    const PackedTensor& shape,
    const char* dtype_json = nullptr,
    const std::string& order = "C",
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(shape);
  detail::JsonBuilder a_;
  if (dtype_json) a_.raw("dtype", dtype_json);
  a_.put_str("order", order);
  return rt.invoke("_npi_ones", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_pad(
    PyRuntime& rt,
    const PackedTensor& array,
    const PackedTensor& pad_width,
    const std::string& mode = "constant",
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(array);
  ins_.push_back(pad_width);
  detail::JsonBuilder a_;
  a_.put_str("mode", mode);
  return rt.invoke("_npi_pad", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_pareto(
    PyRuntime& rt,
    const PackedTensor& a,
    const char* size_json = nullptr,
    const char* dtype_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  if (size_json) a_.raw("size", size_json);
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("_npi_pareto", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_percentile(
    PyRuntime& rt,
    const PackedTensor& a,
    const PackedTensor& q,
    const char* axis_json = nullptr,
    const char* out_json = nullptr,
    bool overwrite_input = false,
    const std::string& method = "linear",
    bool keepdims = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  ins_.push_back(q);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  if (out_json) a_.raw("out", out_json);
  a_.put_bool("overwrite_input", overwrite_input);
  a_.put_str("method", method);
  a_.put_bool("keepdims", keepdims);
  return rt.invoke("_npi_percentile", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_pinv(
    PyRuntime& rt,
    const PackedTensor& a,
    const char* rtol_json = nullptr,
    bool hermitian = false,
    const char* rcond_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  if (rtol_json) a_.raw("rtol", rtol_json);
  a_.put_bool("hermitian", hermitian);
  if (rcond_json) a_.raw("rcond", rcond_json);
  return rt.invoke("_npi_pinv", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_pinv_scalar_rcond(
    PyRuntime& rt,
    const PackedTensor& a,
    const char* rtol_json = nullptr,
    bool hermitian = false,
    const char* rcond_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  if (rtol_json) a_.raw("rtol", rtol_json);
  a_.put_bool("hermitian", hermitian);
  if (rcond_json) a_.raw("rcond", rcond_json);
  return rt.invoke("_npi_pinv_scalar_rcond", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_polyval(
    PyRuntime& rt,
    const PackedTensor& p,
    const PackedTensor& x,
    long long unroll = 16) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(p);
  ins_.push_back(x);
  detail::JsonBuilder a_;
  a_.put_int("unroll", unroll);
  return rt.invoke("_npi_polyval", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_power(
    PyRuntime& rt,
    const PackedTensor& x1,
    const PackedTensor& x2) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x1);
  ins_.push_back(x2);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_power", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_power_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_power_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_powerd(
    PyRuntime& rt,
    const PackedTensor& a,
    const char* size_json = nullptr,
    const char* dtype_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  if (size_json) a_.raw("size", size_json);
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("_npi_powerd", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_prod(
    PyRuntime& rt,
    const PackedTensor& a,
    const char* axis_json = nullptr,
    const char* dtype_json = nullptr,
    const char* out_json = nullptr,
    bool keepdims = false,
    const char* initial_json = nullptr,
    const char* where_json = nullptr,
    bool promote_integers = true) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  if (dtype_json) a_.raw("dtype", dtype_json);
  if (out_json) a_.raw("out", out_json);
  a_.put_bool("keepdims", keepdims);
  if (initial_json) a_.raw("initial", initial_json);
  if (where_json) a_.raw("where", where_json);
  a_.put_bool("promote_integers", promote_integers);
  return rt.invoke("_npi_prod", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_qr(
    PyRuntime& rt,
    const PackedTensor& a,
    const std::string& mode = "reduced") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  a_.put_str("mode", mode);
  return rt.invoke("_npi_qr", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_rad2deg(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_rad2deg", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_radd_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_radd_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_radians(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_radians", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_rarctan2_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_rarctan2_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_rayleigh(
    PyRuntime& rt,
    double scale = 1.0,
    const char* size_json = nullptr,
    const char* dtype_json = nullptr) {
  std::vector<PackedTensor> ins_;
  detail::JsonBuilder a_;
  a_.put_num("scale", scale);
  if (size_json) a_.raw("size", size_json);
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("_npi_rayleigh", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_rbitwise_and_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_rbitwise_and_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_rbitwise_left_shift_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_rbitwise_left_shift_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_rbitwise_or_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_rbitwise_or_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_rbitwise_right_shift_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_rbitwise_right_shift_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_rbitwise_xor_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_rbitwise_xor_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_rcopysign_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_rcopysign_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_reciprocal(
    PyRuntime& rt,
    const PackedTensor& x,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_reciprocal", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_repeat(
    PyRuntime& rt,
    const PackedTensor& a,
    const PackedTensor& repeats,
    const char* axis_json = nullptr,
    const char* total_repeat_length_json = nullptr,
    const char* out_sharding_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  ins_.push_back(repeats);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  if (total_repeat_length_json) a_.raw("total_repeat_length", total_repeat_length_json);
  if (out_sharding_json) a_.raw("out_sharding", out_sharding_json);
  return rt.invoke("_npi_repeat", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_repeats(
    PyRuntime& rt,
    const PackedTensor& a,
    const PackedTensor& repeats,
    const char* axis_json = nullptr,
    const char* total_repeat_length_json = nullptr,
    const char* out_sharding_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  ins_.push_back(repeats);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  if (total_repeat_length_json) a_.raw("total_repeat_length", total_repeat_length_json);
  if (out_sharding_json) a_.raw("out_sharding", out_sharding_json);
  return rt.invoke("_npi_repeats", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_rfloor_divide_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_rfloor_divide_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_rfmax_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_rfmax_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_rfmin_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_rfmin_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_rfmod_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_rfmod_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_rgcd_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_rgcd_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_rint(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_rint", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_rlcm_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_rlcm_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_rldexp_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_rldexp_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_rlogaddexp_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_rlogaddexp_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_rmod_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_rmod_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_rmultiply_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_rmultiply_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_roll(
    PyRuntime& rt,
    const PackedTensor& a,
    const PackedTensor& shift,
    const char* axis_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  ins_.push_back(shift);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  return rt.invoke("_npi_roll", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_rollaxis(
    PyRuntime& rt,
    const PackedTensor& a,
    const PackedTensor& axis,
    long long start = 0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  ins_.push_back(axis);
  detail::JsonBuilder a_;
  a_.put_int("start", start);
  return rt.invoke("_npi_rollaxis", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_rot90(
    PyRuntime& rt,
    const PackedTensor& m,
    long long k = 1,
    const std::vector<long long>& axes = {0, 1}) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(m);
  detail::JsonBuilder a_;
  a_.put_int("k", k);
  a_.put_ivec("axes", axes);
  return rt.invoke("_npi_rot90", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_rpower_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_rpower_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_rsubtract_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_rsubtract_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_rtrue_divide_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_rtrue_divide_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_share_memory(
    PyRuntime& rt,
    const PackedTensor& a,
    const PackedTensor& b) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  ins_.push_back(b);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_share_memory", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_sign(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_sign", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_sin(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_sin", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_sinh(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_sinh", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_solve(
    PyRuntime& rt,
    const PackedTensor& a,
    const PackedTensor& b) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  ins_.push_back(b);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_solve", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_split(
    PyRuntime& rt,
    const PackedTensor& ary,
    const PackedTensor& indices_or_sections,
    long long axis = 0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(ary);
  ins_.push_back(indices_or_sections);
  detail::JsonBuilder a_;
  a_.put_int("axis", axis);
  return rt.invoke("_npi_split", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_sqrt(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_sqrt", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_square(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_square", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_squeeze(
    PyRuntime& rt,
    const PackedTensor& a,
    const char* axis_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  return rt.invoke("_npi_squeeze", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_std(
    PyRuntime& rt,
    const PackedTensor& a,
    const PackedTensor* mean = nullptr,
    const char* axis_json = nullptr,
    const char* dtype_json = nullptr,
    const char* out_json = nullptr,
    long long ddof = 0,
    bool keepdims = false,
    const char* where_json = nullptr,
    const char* correction_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  if (mean) ins_.push_back(*mean);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  if (dtype_json) a_.raw("dtype", dtype_json);
  if (out_json) a_.raw("out", out_json);
  a_.put_int("ddof", ddof);
  a_.put_bool("keepdims", keepdims);
  if (where_json) a_.raw("where", where_json);
  if (correction_json) a_.raw("correction", correction_json);
  return rt.invoke("_npi_std", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_subtract(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* out_json = nullptr,
    const char* where_json = nullptr) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (out_json) a_.raw("out", out_json);
  if (where_json) a_.raw("where", where_json);
  return rt.invoke("_npi_subtract", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_subtract_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_subtract_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_sum(
    PyRuntime& rt,
    const PackedTensor& a,
    const char* axis_json = nullptr,
    const char* dtype_json = nullptr,
    const char* out_json = nullptr,
    bool keepdims = false,
    const char* initial_json = nullptr,
    const char* where_json = nullptr,
    bool promote_integers = true) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  if (dtype_json) a_.raw("dtype", dtype_json);
  if (out_json) a_.raw("out", out_json);
  a_.put_bool("keepdims", keepdims);
  if (initial_json) a_.raw("initial", initial_json);
  if (where_json) a_.raw("where", where_json);
  a_.put_bool("promote_integers", promote_integers);
  return rt.invoke("_npi_sum", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_svd(
    PyRuntime& rt,
    const PackedTensor& a) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_svd", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_tan(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_tan", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_tanh(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_tanh", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_tensordot(
    PyRuntime& rt,
    const PackedTensor& a,
    const PackedTensor& b,
    long long axes = 2,
    const char* precision_json = nullptr,
    const char* preferred_element_type_json = nullptr,
    const char* out_sharding_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  ins_.push_back(b);
  detail::JsonBuilder a_;
  a_.put_int("axes", axes);
  if (precision_json) a_.raw("precision", precision_json);
  if (preferred_element_type_json) a_.raw("preferred_element_type", preferred_element_type_json);
  if (out_sharding_json) a_.raw("out_sharding", out_sharding_json);
  return rt.invoke("_npi_tensordot", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_tensordot_int_axes(
    PyRuntime& rt,
    const PackedTensor& a,
    const PackedTensor& b,
    long long axes = 2,
    const char* precision_json = nullptr,
    const char* preferred_element_type_json = nullptr,
    const char* out_sharding_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  ins_.push_back(b);
  detail::JsonBuilder a_;
  a_.put_int("axes", axes);
  if (precision_json) a_.raw("precision", precision_json);
  if (preferred_element_type_json) a_.raw("preferred_element_type", preferred_element_type_json);
  if (out_sharding_json) a_.raw("out_sharding", out_sharding_json);
  return rt.invoke("_npi_tensordot_int_axes", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_tensorinv(
    PyRuntime& rt,
    const PackedTensor& a,
    long long ind = 2) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  a_.put_int("ind", ind);
  return rt.invoke("_npi_tensorinv", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_tensorsolve(
    PyRuntime& rt,
    const PackedTensor& a,
    const PackedTensor& b,
    const char* axes_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  ins_.push_back(b);
  detail::JsonBuilder a_;
  if (axes_json) a_.raw("axes", axes_json);
  return rt.invoke("_npi_tensorsolve", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_trace(
    PyRuntime& rt,
    const PackedTensor& a,
    long long offset = 0,
    long long axis1 = 0,
    long long axis2 = 1,
    const char* dtype_json = nullptr,
    const char* out_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  a_.put_int("offset", offset);
  a_.put_int("axis1", axis1);
  a_.put_int("axis2", axis2);
  if (dtype_json) a_.raw("dtype", dtype_json);
  if (out_json) a_.raw("out", out_json);
  return rt.invoke("_npi_trace", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_transpose(
    PyRuntime& rt,
    const PackedTensor& a,
    const char* axes_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  if (axes_json) a_.raw("axes", axes_json);
  return rt.invoke("_npi_transpose", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_tri(
    PyRuntime& rt,
    const PackedTensor& N,
    const char* M_json = nullptr,
    long long k = 0,
    const char* dtype_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(N);
  detail::JsonBuilder a_;
  if (M_json) a_.raw("M", M_json);
  a_.put_int("k", k);
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("_npi_tri", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_tril(
    PyRuntime& rt,
    const PackedTensor& m,
    long long k = 0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(m);
  detail::JsonBuilder a_;
  a_.put_int("k", k);
  return rt.invoke("_npi_tril", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_tril_indices(
    PyRuntime& rt,
    const PackedTensor& n,
    long long k = 0,
    const char* m_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(n);
  detail::JsonBuilder a_;
  a_.put_int("k", k);
  if (m_json) a_.raw("m", m_json);
  return rt.invoke("_npi_tril_indices", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_triu(
    PyRuntime& rt,
    const PackedTensor& m,
    long long k = 0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(m);
  detail::JsonBuilder a_;
  a_.put_int("k", k);
  return rt.invoke("_npi_triu", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_true_divide(
    PyRuntime& rt,
    const PackedTensor& x1,
    const PackedTensor& x2) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x1);
  ins_.push_back(x2);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_true_divide", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_true_divide_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_npi_true_divide_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_trunc(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npi_trunc", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_uniform(
    PyRuntime& rt,
    double low = 0.0,
    double high = 1.0,
    const char* size_json = nullptr,
    const char* dtype_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  detail::JsonBuilder a_;
  a_.put_num("low", low);
  a_.put_num("high", high);
  if (size_json) a_.raw("size", size_json);
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("_npi_uniform", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_uniform_n(
    PyRuntime& rt,
    double low = 0.0,
    double high = 1.0,
    const char* size_json = nullptr,
    const char* dtype_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  detail::JsonBuilder a_;
  a_.put_num("low", low);
  a_.put_num("high", high);
  if (size_json) a_.raw("size", size_json);
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("_npi_uniform_n", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npi_unique(
    PyRuntime& rt,
    const PackedTensor& ar,
    bool return_index = false,
    bool return_inverse = false,
    bool return_counts = false,
    const char* axis_json = nullptr,
    bool equal_nan = true,
    const char* size_json = nullptr,
    const char* fill_value_json = nullptr,
    bool sorted = true) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(ar);
  detail::JsonBuilder a_;
  a_.put_bool("return_index", return_index);
  a_.put_bool("return_inverse", return_inverse);
  a_.put_bool("return_counts", return_counts);
  if (axis_json) a_.raw("axis", axis_json);
  a_.put_bool("equal_nan", equal_nan);
  if (size_json) a_.raw("size", size_json);
  if (fill_value_json) a_.raw("fill_value", fill_value_json);
  a_.put_bool("sorted", sorted);
  return rt.invoke("_npi_unique", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_var(
    PyRuntime& rt,
    const PackedTensor& a,
    const PackedTensor* mean = nullptr,
    const char* axis_json = nullptr,
    const char* dtype_json = nullptr,
    const char* out_json = nullptr,
    long long ddof = 0,
    bool keepdims = false,
    const char* where_json = nullptr,
    const char* correction_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  if (mean) ins_.push_back(*mean);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  if (dtype_json) a_.raw("dtype", dtype_json);
  if (out_json) a_.raw("out", out_json);
  a_.put_int("ddof", ddof);
  a_.put_bool("keepdims", keepdims);
  if (where_json) a_.raw("where", where_json);
  if (correction_json) a_.raw("correction", correction_json);
  return rt.invoke("_npi_var", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_vstack(
    PyRuntime& rt,
    const PackedTensor& tup,
    const char* dtype_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(tup);
  detail::JsonBuilder a_;
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("_npi_vstack", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_weibull(
    PyRuntime& rt,
    const PackedTensor& a,
    const char* size_json = nullptr,
    const char* dtype_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  if (size_json) a_.raw("size", size_json);
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("_npi_weibull", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_where(
    PyRuntime& rt,
    const PackedTensor& condition,
    const char* x_json = nullptr,
    const char* y_json = nullptr,
    const char* size_json = nullptr,
    const char* fill_value_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(condition);
  detail::JsonBuilder a_;
  if (x_json) a_.raw("x", x_json);
  if (y_json) a_.raw("y", y_json);
  if (size_json) a_.raw("size", size_json);
  if (fill_value_json) a_.raw("fill_value", fill_value_json);
  return rt.invoke("_npi_where", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_where_lscalar(
    PyRuntime& rt,
    const PackedTensor& cond,
    const PackedTensor& y,
    double scalar = 0.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(cond);
  ins_.push_back(y);
  detail::JsonBuilder a_;
  a_.put_num("scalar", scalar);
  return rt.invoke("_npi_where_lscalar", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_where_rscalar(
    PyRuntime& rt,
    const PackedTensor& cond,
    const PackedTensor& x,
    double scalar = 0.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(cond);
  ins_.push_back(x);
  detail::JsonBuilder a_;
  a_.put_num("scalar", scalar);
  return rt.invoke("_npi_where_rscalar", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_where_scalar2(
    PyRuntime& rt,
    const PackedTensor& cond,
    double x = 0.0,
    double y = 0.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(cond);
  detail::JsonBuilder a_;
  a_.put_num("x", x);
  a_.put_num("y", y);
  return rt.invoke("_npi_where_scalar2", ins_, a_.str());
}

inline std::vector<PackedTensor> _npi_zeros(
    PyRuntime& rt,
    const PackedTensor& shape,
    const char* dtype_json = nullptr,
    const std::string& order = "C",
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(shape);
  detail::JsonBuilder a_;
  if (dtype_json) a_.raw("dtype", dtype_json);
  a_.put_str("order", order);
  return rt.invoke("_npi_zeros", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npx_box_decode(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& anchors,
    double std0 = 0.1,
    double std1 = 0.1,
    double std2 = 0.2,
    double std3 = 0.2,
    double clip = -1.0,
    const std::string& format = "corner") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(anchors);
  detail::JsonBuilder a_;
  a_.put_num("std0", std0);
  a_.put_num("std1", std1);
  a_.put_num("std2", std2);
  a_.put_num("std3", std3);
  a_.put_num("clip", clip);
  a_.put_str("format", format);
  return rt.invoke("_npx_box_decode", ins_, a_.str());
}

inline std::vector<PackedTensor> _npx_box_encode(
    PyRuntime& rt,
    const PackedTensor& samples,
    const PackedTensor& matches,
    const PackedTensor& anchors,
    const PackedTensor& refs,
    const std::vector<double>& means = {0.0, 0.0, 0.0, 0.0},
    const std::vector<double>& stds = {0.1, 0.1, 0.2, 0.2}) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(samples);
  ins_.push_back(matches);
  ins_.push_back(anchors);
  ins_.push_back(refs);
  detail::JsonBuilder a_;
  a_.put_fvec("means", means);
  a_.put_fvec("stds", stds);
  return rt.invoke("_npx_box_encode", ins_, a_.str());
}

inline std::vector<PackedTensor> _npx_cond(
    PyRuntime& rt,
    const PackedTensor& pred,
    const PackedTensor& then_func,
    const PackedTensor& else_func,
    const std::vector<long long>& inputs = {}) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(pred);
  ins_.push_back(then_func);
  ins_.push_back(else_func);
  detail::JsonBuilder a_;
  a_.put_ivec("inputs", inputs);
  return rt.invoke("_npx_cond", ins_, a_.str());
}

inline std::vector<PackedTensor> _npx_constraint_check(
    PyRuntime& rt,
    const PackedTensor& condition,
    const std::string& msg = "Constraint violated") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(condition);
  detail::JsonBuilder a_;
  a_.put_str("msg", msg);
  return rt.invoke("_npx_constraint_check", ins_, a_.str());
}

inline std::vector<PackedTensor> _npx_foreach(
    PyRuntime& rt,
    const PackedTensor& body,
    const PackedTensor& data,
    const PackedTensor& init_states) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(body);
  ins_.push_back(data);
  ins_.push_back(init_states);
  detail::JsonBuilder a_;
  return rt.invoke("_npx_foreach", ins_, a_.str());
}

inline std::vector<PackedTensor> _npx_index_add(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& indices,
    const PackedTensor& val) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(indices);
  ins_.push_back(val);
  detail::JsonBuilder a_;
  return rt.invoke("_npx_index_add", ins_, a_.str());
}

inline std::vector<PackedTensor> _npx_index_update(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& indices,
    const PackedTensor& val) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(indices);
  ins_.push_back(val);
  detail::JsonBuilder a_;
  return rt.invoke("_npx_index_update", ins_, a_.str());
}

inline std::vector<PackedTensor> _npx_nonzero(
    PyRuntime& rt,
    const PackedTensor& data) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  return rt.invoke("_npx_nonzero", ins_, a_.str());
}

inline std::vector<PackedTensor> _npx_relu(
    PyRuntime& rt,
    const PackedTensor& x,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npx_relu", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npx_reshape(
    PyRuntime& rt,
    const PackedTensor& a,
    const PackedTensor& newshape,
    bool reverse = false,
    const std::string& order = "C") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  ins_.push_back(newshape);
  detail::JsonBuilder a_;
  a_.put_bool("reverse", reverse);
  a_.put_str("order", order);
  return rt.invoke("_npx_reshape", ins_, a_.str());
}

inline std::vector<PackedTensor> _npx_sigmoid(
    PyRuntime& rt,
    const PackedTensor& x,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_npx_sigmoid", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _npx_sldwin_atten_context(
    PyRuntime& rt,
    const PackedTensor& score,
    const PackedTensor& value,
    const PackedTensor& dilation,
    long long w = 2,
    bool symmetric = true) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(score);
  ins_.push_back(value);
  ins_.push_back(dilation);
  detail::JsonBuilder a_;
  a_.put_int("w", w);
  a_.put_bool("symmetric", symmetric);
  return rt.invoke("_npx_sldwin_atten_context", ins_, a_.str());
}

inline std::vector<PackedTensor> _npx_sldwin_atten_mask_like(
    PyRuntime& rt,
    const PackedTensor& score,
    const PackedTensor& dilation,
    const PackedTensor& valid_length,
    const char* num_heads_json = nullptr,
    long long w = 2,
    bool symmetric = true) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(score);
  ins_.push_back(dilation);
  ins_.push_back(valid_length);
  detail::JsonBuilder a_;
  if (num_heads_json) a_.raw("num_heads", num_heads_json);
  a_.put_int("w", w);
  a_.put_bool("symmetric", symmetric);
  return rt.invoke("_npx_sldwin_atten_mask_like", ins_, a_.str());
}

inline std::vector<PackedTensor> _npx_sldwin_atten_score(
    PyRuntime& rt,
    const PackedTensor& query,
    const PackedTensor& key,
    const PackedTensor& dilation,
    long long w = 2,
    bool symmetric = true) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(query);
  ins_.push_back(key);
  ins_.push_back(dilation);
  detail::JsonBuilder a_;
  a_.put_int("w", w);
  a_.put_bool("symmetric", symmetric);
  return rt.invoke("_npx_sldwin_atten_score", ins_, a_.str());
}

inline std::vector<PackedTensor> _npx_while_loop(
    PyRuntime& rt,
    const PackedTensor& cond,
    const PackedTensor& func,
    const PackedTensor& loop_vars,
    const char* max_iterations_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(cond);
  ins_.push_back(func);
  ins_.push_back(loop_vars);
  detail::JsonBuilder a_;
  if (max_iterations_json) a_.raw("max_iterations", max_iterations_json);
  return rt.invoke("_npx_while_loop", ins_, a_.str());
}

inline std::vector<PackedTensor> _ones(
    PyRuntime& rt,
    const PackedTensor& shape,
    const char* dtype_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(shape);
  detail::JsonBuilder a_;
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("_ones", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _plus_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_plus_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _power(
    PyRuntime& rt,
    const PackedTensor& x1,
    const PackedTensor& x2) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x1);
  ins_.push_back(x2);
  detail::JsonBuilder a_;
  return rt.invoke("_power", ins_, a_.str());
}

inline std::vector<PackedTensor> _power_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_power_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _random_pdf_dirichlet(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& sample,
    bool is_log = false,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(sample);
  detail::JsonBuilder a_;
  a_.put_bool("is_log", is_log);
  return rt.invoke("_random_pdf_dirichlet", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _random_pdf_exponential(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& sample,
    bool is_log = false,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(sample);
  detail::JsonBuilder a_;
  a_.put_bool("is_log", is_log);
  return rt.invoke("_random_pdf_exponential", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _random_pdf_gamma(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& sample,
    bool is_log = false,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(sample);
  detail::JsonBuilder a_;
  a_.put_bool("is_log", is_log);
  return rt.invoke("_random_pdf_gamma", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _random_pdf_generalized_negative_binomial(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& sample,
    bool is_log = false,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(sample);
  detail::JsonBuilder a_;
  a_.put_bool("is_log", is_log);
  return rt.invoke("_random_pdf_generalized_negative_binomial", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _random_pdf_negative_binomial(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& sample,
    bool is_log = false,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(sample);
  detail::JsonBuilder a_;
  a_.put_bool("is_log", is_log);
  return rt.invoke("_random_pdf_negative_binomial", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _random_pdf_normal(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& sample,
    bool is_log = false,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(sample);
  detail::JsonBuilder a_;
  a_.put_bool("is_log", is_log);
  return rt.invoke("_random_pdf_normal", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _random_pdf_poisson(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& sample,
    bool is_log = false,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(sample);
  detail::JsonBuilder a_;
  a_.put_bool("is_log", is_log);
  return rt.invoke("_random_pdf_poisson", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _random_pdf_uniform(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& sample,
    bool is_log = false,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(sample);
  detail::JsonBuilder a_;
  a_.put_bool("is_log", is_log);
  return rt.invoke("_random_pdf_uniform", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _ravel_multi_index(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& shape) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(shape);
  detail::JsonBuilder a_;
  return rt.invoke("_ravel_multi_index", ins_, a_.str());
}

inline std::vector<PackedTensor> _rdiv_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_rdiv_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _rminus_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_rminus_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _rmod_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_rmod_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _rnn_param_concat(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    long long dim = 0,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  a_.put_int("dim", dim);
  return rt.invoke("_rnn_param_concat", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _rpower_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_rpower_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _sample_exponential(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* shape_json = nullptr,
    const char* dtype_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (shape_json) a_.raw("shape", shape_json);
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("_sample_exponential", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _sample_gamma(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* shape_json = nullptr,
    const char* dtype_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (shape_json) a_.raw("shape", shape_json);
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("_sample_gamma", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _sample_generalized_negative_binomial(
    PyRuntime& rt,
    double mu = 1.0,
    double alpha = 1.0,
    const char* shape_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  detail::JsonBuilder a_;
  a_.put_num("mu", mu);
  a_.put_num("alpha", alpha);
  if (shape_json) a_.raw("shape", shape_json);
  return rt.invoke("_sample_generalized_negative_binomial", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _sample_multinomial(
    PyRuntime& rt,
    const PackedTensor& data,
    const char* shape_json = nullptr,
    bool get_prob = false,
    const std::string& dtype = "int32",
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (shape_json) a_.raw("shape", shape_json);
  a_.put_bool("get_prob", get_prob);
  a_.put_str("dtype", dtype);
  return rt.invoke("_sample_multinomial", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _sample_negative_binomial(
    PyRuntime& rt,
    long long k = 1,
    double p = 0.5,
    const char* shape_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  detail::JsonBuilder a_;
  a_.put_int("k", k);
  a_.put_num("p", p);
  if (shape_json) a_.raw("shape", shape_json);
  return rt.invoke("_sample_negative_binomial", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _sample_normal(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* shape_json = nullptr,
    const char* dtype_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (shape_json) a_.raw("shape", shape_json);
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("_sample_normal", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _sample_poisson(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* shape_json = nullptr,
    const char* dtype_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (shape_json) a_.raw("shape", shape_json);
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("_sample_poisson", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _sample_uniform(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* shape_json = nullptr,
    const char* dtype_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (shape_json) a_.raw("shape", shape_json);
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("_sample_uniform", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _sample_unique_zipfian(
    PyRuntime& rt,
    const PackedTensor& range_max,
    const char* shape_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(range_max);
  detail::JsonBuilder a_;
  if (shape_json) a_.raw("shape", shape_json);
  return rt.invoke("_sample_unique_zipfian", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _scatter_set_nd(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& indices,
    const PackedTensor& val) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(indices);
  ins_.push_back(val);
  detail::JsonBuilder a_;
  return rt.invoke("_scatter_set_nd", ins_, a_.str());
}

inline std::vector<PackedTensor> _shuffle(
    PyRuntime& rt,
    const PackedTensor& data,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  return rt.invoke("_shuffle", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _slice_assign(
    PyRuntime& rt,
    const PackedTensor& lhs,
    const PackedTensor& rhs,
    const PackedTensor& begin,
    const PackedTensor& end,
    const char* step_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(lhs);
  ins_.push_back(rhs);
  ins_.push_back(begin);
  ins_.push_back(end);
  detail::JsonBuilder a_;
  if (step_json) a_.raw("step", step_json);
  return rt.invoke("_slice_assign", ins_, a_.str());
}

inline std::vector<PackedTensor> _slice_assign_scalar(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& data,
    const char* scalar_json = nullptr,
    const char* is_int_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (scalar_json) a_.raw("scalar", scalar_json);
  if (is_int_json) a_.raw("is_int", is_int_json);
  return rt.invoke("_slice_assign_scalar", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _sparse_adagrad_update(
    PyRuntime& rt,
    const PackedTensor& weight,
    const PackedTensor& grad,
    const PackedTensor& history,
    const PackedTensor& lr,
    double epsilon = 1e-07,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(weight);
  ins_.push_back(grad);
  ins_.push_back(history);
  ins_.push_back(lr);
  detail::JsonBuilder a_;
  a_.put_num("epsilon", epsilon);
  a_.put_num("wd", wd);
  a_.put_num("rescale_grad", rescale_grad);
  a_.put_num("clip_gradient", clip_gradient);
  return rt.invoke("_sparse_adagrad_update", ins_, a_.str());
}

inline std::vector<PackedTensor> _sparse_retain(
    PyRuntime& rt,
    const PackedTensor& rsp,
    const PackedTensor& indices) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(rsp);
  ins_.push_back(indices);
  detail::JsonBuilder a_;
  return rt.invoke("_sparse_retain", ins_, a_.str());
}

inline std::vector<PackedTensor> _split_v2(
    PyRuntime& rt,
    const PackedTensor& ary,
    const PackedTensor& indices_or_sections,
    long long axis = 0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(ary);
  ins_.push_back(indices_or_sections);
  detail::JsonBuilder a_;
  a_.put_int("axis", axis);
  return rt.invoke("_split_v2", ins_, a_.str());
}

inline std::vector<PackedTensor> _square_sum(
    PyRuntime& rt,
    const PackedTensor& x,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("_square_sum", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _unravel_index(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& shape) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(shape);
  detail::JsonBuilder a_;
  return rt.invoke("_unravel_index", ins_, a_.str());
}

inline std::vector<PackedTensor> _while_loop(
    PyRuntime& rt,
    const PackedTensor& cond,
    const PackedTensor& func,
    const PackedTensor& loop_vars,
    const char* max_iterations_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(cond);
  ins_.push_back(func);
  ins_.push_back(loop_vars);
  detail::JsonBuilder a_;
  if (max_iterations_json) a_.raw("max_iterations", max_iterations_json);
  return rt.invoke("_while_loop", ins_, a_.str());
}

inline std::vector<PackedTensor> _zeros(
    PyRuntime& rt,
    const PackedTensor& shape,
    const char* dtype_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(shape);
  detail::JsonBuilder a_;
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("_zeros", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> _zeros_without_dtype(
    PyRuntime& rt,
    const PackedTensor& shape,
    const char* dtype_json = nullptr,
    const char* device_json = nullptr,
    const char* out_sharding_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(shape);
  detail::JsonBuilder a_;
  if (dtype_json) a_.raw("dtype", dtype_json);
  if (device_json) a_.raw("device", device_json);
  if (out_sharding_json) a_.raw("out_sharding", out_sharding_json);
  return rt.invoke("_zeros_without_dtype", ins_, a_.str());
}

inline std::vector<PackedTensor> abs(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("abs", ins_, a_.str());
}

inline std::vector<PackedTensor> activation(
    PyRuntime& rt,
    const PackedTensor& x,
    const std::string& act_type = "relu") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  a_.put_str("act_type", act_type);
  return rt.invoke("activation", ins_, a_.str());
}

inline std::vector<PackedTensor> adabelief_update(
    PyRuntime& rt,
    const PackedTensor& weight,
    const PackedTensor& grad,
    const PackedTensor& mean,
    const PackedTensor& var,
    const PackedTensor& lr,
    double beta1 = 0.9,
    double beta2 = 0.999,
    double epsilon = 1e-08,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(weight);
  ins_.push_back(grad);
  ins_.push_back(mean);
  ins_.push_back(var);
  ins_.push_back(lr);
  detail::JsonBuilder a_;
  a_.put_num("beta1", beta1);
  a_.put_num("beta2", beta2);
  a_.put_num("epsilon", epsilon);
  a_.put_num("wd", wd);
  a_.put_num("rescale_grad", rescale_grad);
  a_.put_num("clip_gradient", clip_gradient);
  return rt.invoke("adabelief_update", ins_, a_.str());
}

inline std::vector<PackedTensor> adadelta_update(
    PyRuntime& rt,
    const PackedTensor& weight,
    const PackedTensor& grad,
    const PackedTensor& acc_g,
    const PackedTensor& acc_delta,
    double rho = 0.9,
    double epsilon = 1e-05,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(weight);
  ins_.push_back(grad);
  ins_.push_back(acc_g);
  ins_.push_back(acc_delta);
  detail::JsonBuilder a_;
  a_.put_num("rho", rho);
  a_.put_num("epsilon", epsilon);
  a_.put_num("wd", wd);
  a_.put_num("rescale_grad", rescale_grad);
  a_.put_num("clip_gradient", clip_gradient);
  return rt.invoke("adadelta_update", ins_, a_.str());
}

inline std::vector<PackedTensor> adagrad_update(
    PyRuntime& rt,
    const PackedTensor& weight,
    const PackedTensor& grad,
    const PackedTensor& history,
    const PackedTensor& lr,
    double epsilon = 1e-07,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(weight);
  ins_.push_back(grad);
  ins_.push_back(history);
  ins_.push_back(lr);
  detail::JsonBuilder a_;
  a_.put_num("epsilon", epsilon);
  a_.put_num("wd", wd);
  a_.put_num("rescale_grad", rescale_grad);
  a_.put_num("clip_gradient", clip_gradient);
  return rt.invoke("adagrad_update", ins_, a_.str());
}

inline std::vector<PackedTensor> adam_update(
    PyRuntime& rt,
    const PackedTensor& weight,
    const PackedTensor& grad,
    const PackedTensor& mean,
    const PackedTensor& var,
    const PackedTensor& lr,
    double beta1 = 0.9,
    double beta2 = 0.999,
    double epsilon = 1e-08,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    bool lazy_update = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(weight);
  ins_.push_back(grad);
  ins_.push_back(mean);
  ins_.push_back(var);
  ins_.push_back(lr);
  detail::JsonBuilder a_;
  a_.put_num("beta1", beta1);
  a_.put_num("beta2", beta2);
  a_.put_num("epsilon", epsilon);
  a_.put_num("wd", wd);
  a_.put_num("rescale_grad", rescale_grad);
  a_.put_num("clip_gradient", clip_gradient);
  a_.put_bool("lazy_update", lazy_update);
  return rt.invoke("adam_update", ins_, a_.str());
}

inline std::vector<PackedTensor> adamw_update(
    PyRuntime& rt,
    const PackedTensor& weight,
    const PackedTensor& grad,
    const PackedTensor& mean,
    const PackedTensor& var,
    const PackedTensor& lr,
    double beta1 = 0.9,
    double beta2 = 0.999,
    double epsilon = 1e-08,
    double wd = 0.0,
    double eta = 1.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(weight);
  ins_.push_back(grad);
  ins_.push_back(mean);
  ins_.push_back(var);
  ins_.push_back(lr);
  detail::JsonBuilder a_;
  a_.put_num("beta1", beta1);
  a_.put_num("beta2", beta2);
  a_.put_num("epsilon", epsilon);
  a_.put_num("wd", wd);
  a_.put_num("eta", eta);
  a_.put_num("rescale_grad", rescale_grad);
  a_.put_num("clip_gradient", clip_gradient);
  return rt.invoke("adamw_update", ins_, a_.str());
}

inline std::vector<PackedTensor> add_n(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  return rt.invoke("add_n", ins_, a_.str());
}

inline std::vector<PackedTensor> all_finite(
    PyRuntime& rt,
    const PackedTensor& data,
    bool init_output = true) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  a_.put_bool("init_output", init_output);
  return rt.invoke("all_finite", ins_, a_.str());
}

inline std::vector<PackedTensor> amp_cast(
    PyRuntime& rt,
    const PackedTensor& x,
    const PackedTensor& dtype) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  ins_.push_back(dtype);
  detail::JsonBuilder a_;
  return rt.invoke("amp_cast", ins_, a_.str());
}

inline std::vector<PackedTensor> amp_multicast(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* num_outputs_json = nullptr,
    bool cast_narrow = false) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (num_outputs_json) a_.raw("num_outputs", num_outputs_json);
  a_.put_bool("cast_narrow", cast_narrow);
  return rt.invoke("amp_multicast", ins_, a_.str());
}

inline std::vector<PackedTensor> arange_like(
    PyRuntime& rt,
    const PackedTensor& data,
    double start = 0.0,
    double step = 1.0,
    long long repeat = 1,
    const char* axis_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  a_.put_num("start", start);
  a_.put_num("step", step);
  a_.put_int("repeat", repeat);
  if (axis_json) a_.raw("axis", axis_json);
  return rt.invoke("arange_like", ins_, a_.str());
}

inline std::vector<PackedTensor> arccos(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("arccos", ins_, a_.str());
}

inline std::vector<PackedTensor> arccosh(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("arccosh", ins_, a_.str());
}

inline std::vector<PackedTensor> arcsin(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("arcsin", ins_, a_.str());
}

inline std::vector<PackedTensor> arcsinh(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("arcsinh", ins_, a_.str());
}

inline std::vector<PackedTensor> arctan(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("arctan", ins_, a_.str());
}

inline std::vector<PackedTensor> arctanh(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("arctanh", ins_, a_.str());
}

inline std::vector<PackedTensor> argmax(
    PyRuntime& rt,
    const PackedTensor& data,
    const char* axis_json = nullptr,
    bool keepdims = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  a_.put_bool("keepdims", keepdims);
  return rt.invoke("argmax", ins_, a_.str());
}

inline std::vector<PackedTensor> argmax_channel(
    PyRuntime& rt,
    const PackedTensor& data) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  return rt.invoke("argmax_channel", ins_, a_.str());
}

inline std::vector<PackedTensor> argmin(
    PyRuntime& rt,
    const PackedTensor& data,
    const char* axis_json = nullptr,
    bool keepdims = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  a_.put_bool("keepdims", keepdims);
  return rt.invoke("argmin", ins_, a_.str());
}

inline std::vector<PackedTensor> argsort(
    PyRuntime& rt,
    const PackedTensor& data,
    long long axis = -1,
    bool is_ascend = true,
    const char* dtype_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  a_.put_int("axis", axis);
  a_.put_bool("is_ascend", is_ascend);
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("argsort", ins_, a_.str());
}

inline std::vector<PackedTensor> batch_dot(
    PyRuntime& rt,
    const PackedTensor& lhs,
    const PackedTensor& rhs,
    bool transpose_a = false,
    bool transpose_b = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(lhs);
  ins_.push_back(rhs);
  detail::JsonBuilder a_;
  a_.put_bool("transpose_a", transpose_a);
  a_.put_bool("transpose_b", transpose_b);
  return rt.invoke("batch_dot", ins_, a_.str());
}

inline std::vector<PackedTensor> batch_norm(
    PyRuntime& rt,
    const PackedTensor& x,
    const PackedTensor& gamma,
    const PackedTensor& beta,
    const PackedTensor& moving_mean,
    const PackedTensor& moving_var,
    double eps = 1e-05,
    double momentum = 0.9,
    bool training = true,
    bool use_global_stats = false,
    long long axis = 1) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  ins_.push_back(gamma);
  ins_.push_back(beta);
  ins_.push_back(moving_mean);
  ins_.push_back(moving_var);
  detail::JsonBuilder a_;
  a_.put_num("eps", eps);
  a_.put_num("momentum", momentum);
  a_.put_bool("training", training);
  a_.put_bool("use_global_stats", use_global_stats);
  a_.put_int("axis", axis);
  return rt.invoke("batch_norm", ins_, a_.str());
}

inline std::vector<PackedTensor> batch_take(
    PyRuntime& rt,
    const PackedTensor& a,
    const PackedTensor& indices) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  ins_.push_back(indices);
  detail::JsonBuilder a_;
  return rt.invoke("batch_take", ins_, a_.str());
}

inline std::vector<PackedTensor> broadcast_add(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* out_json = nullptr,
    const char* where_json = nullptr) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (out_json) a_.raw("out", out_json);
  if (where_json) a_.raw("where", where_json);
  return rt.invoke("broadcast_add", ins_, a_.str());
}

inline std::vector<PackedTensor> broadcast_axes(
    PyRuntime& rt,
    const PackedTensor& data,
    const char* axis_json = nullptr,
    const char* size_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  if (size_json) a_.raw("size", size_json);
  return rt.invoke("broadcast_axes", ins_, a_.str());
}

inline std::vector<PackedTensor> broadcast_axis(
    PyRuntime& rt,
    const PackedTensor& data,
    const char* axis_json = nullptr,
    const char* size_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  if (size_json) a_.raw("size", size_json);
  return rt.invoke("broadcast_axis", ins_, a_.str());
}

inline std::vector<PackedTensor> broadcast_div(
    PyRuntime& rt,
    const PackedTensor& x1,
    const PackedTensor& x2) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x1);
  ins_.push_back(x2);
  detail::JsonBuilder a_;
  return rt.invoke("broadcast_div", ins_, a_.str());
}

inline std::vector<PackedTensor> broadcast_equal(
    PyRuntime& rt,
    const PackedTensor& lhs,
    const PackedTensor& rhs) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(lhs);
  ins_.push_back(rhs);
  detail::JsonBuilder a_;
  return rt.invoke("broadcast_equal", ins_, a_.str());
}

inline std::vector<PackedTensor> broadcast_greater(
    PyRuntime& rt,
    const PackedTensor& lhs,
    const PackedTensor& rhs) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(lhs);
  ins_.push_back(rhs);
  detail::JsonBuilder a_;
  return rt.invoke("broadcast_greater", ins_, a_.str());
}

inline std::vector<PackedTensor> broadcast_greater_equal(
    PyRuntime& rt,
    const PackedTensor& lhs,
    const PackedTensor& rhs) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(lhs);
  ins_.push_back(rhs);
  detail::JsonBuilder a_;
  return rt.invoke("broadcast_greater_equal", ins_, a_.str());
}

inline std::vector<PackedTensor> broadcast_hypot(
    PyRuntime& rt,
    const PackedTensor& x1,
    const PackedTensor& x2) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x1);
  ins_.push_back(x2);
  detail::JsonBuilder a_;
  return rt.invoke("broadcast_hypot", ins_, a_.str());
}

inline std::vector<PackedTensor> broadcast_lesser(
    PyRuntime& rt,
    const PackedTensor& lhs,
    const PackedTensor& rhs) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(lhs);
  ins_.push_back(rhs);
  detail::JsonBuilder a_;
  return rt.invoke("broadcast_lesser", ins_, a_.str());
}

inline std::vector<PackedTensor> broadcast_lesser_equal(
    PyRuntime& rt,
    const PackedTensor& lhs,
    const PackedTensor& rhs) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(lhs);
  ins_.push_back(rhs);
  detail::JsonBuilder a_;
  return rt.invoke("broadcast_lesser_equal", ins_, a_.str());
}

inline std::vector<PackedTensor> broadcast_like(
    PyRuntime& rt,
    const PackedTensor& lhs,
    const PackedTensor& rhs) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(lhs);
  ins_.push_back(rhs);
  detail::JsonBuilder a_;
  return rt.invoke("broadcast_like", ins_, a_.str());
}

inline std::vector<PackedTensor> broadcast_logical_and(
    PyRuntime& rt,
    const PackedTensor& lhs,
    const PackedTensor& rhs) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(lhs);
  ins_.push_back(rhs);
  detail::JsonBuilder a_;
  return rt.invoke("broadcast_logical_and", ins_, a_.str());
}

inline std::vector<PackedTensor> broadcast_logical_or(
    PyRuntime& rt,
    const PackedTensor& lhs,
    const PackedTensor& rhs) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(lhs);
  ins_.push_back(rhs);
  detail::JsonBuilder a_;
  return rt.invoke("broadcast_logical_or", ins_, a_.str());
}

inline std::vector<PackedTensor> broadcast_logical_xor(
    PyRuntime& rt,
    const PackedTensor& lhs,
    const PackedTensor& rhs) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(lhs);
  ins_.push_back(rhs);
  detail::JsonBuilder a_;
  return rt.invoke("broadcast_logical_xor", ins_, a_.str());
}

inline std::vector<PackedTensor> broadcast_maximum(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* out_json = nullptr,
    const char* where_json = nullptr) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (out_json) a_.raw("out", out_json);
  if (where_json) a_.raw("where", where_json);
  return rt.invoke("broadcast_maximum", ins_, a_.str());
}

inline std::vector<PackedTensor> broadcast_minimum(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* out_json = nullptr,
    const char* where_json = nullptr) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (out_json) a_.raw("out", out_json);
  if (where_json) a_.raw("where", where_json);
  return rt.invoke("broadcast_minimum", ins_, a_.str());
}

inline std::vector<PackedTensor> broadcast_minus(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* out_json = nullptr,
    const char* where_json = nullptr) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (out_json) a_.raw("out", out_json);
  if (where_json) a_.raw("where", where_json);
  return rt.invoke("broadcast_minus", ins_, a_.str());
}

inline std::vector<PackedTensor> broadcast_mod(
    PyRuntime& rt,
    const PackedTensor& x1,
    const PackedTensor& x2) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x1);
  ins_.push_back(x2);
  detail::JsonBuilder a_;
  return rt.invoke("broadcast_mod", ins_, a_.str());
}

inline std::vector<PackedTensor> broadcast_mul(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* out_json = nullptr,
    const char* where_json = nullptr) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (out_json) a_.raw("out", out_json);
  if (where_json) a_.raw("where", where_json);
  return rt.invoke("broadcast_mul", ins_, a_.str());
}

inline std::vector<PackedTensor> broadcast_not_equal(
    PyRuntime& rt,
    const PackedTensor& lhs,
    const PackedTensor& rhs) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(lhs);
  ins_.push_back(rhs);
  detail::JsonBuilder a_;
  return rt.invoke("broadcast_not_equal", ins_, a_.str());
}

inline std::vector<PackedTensor> broadcast_plus(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* out_json = nullptr,
    const char* where_json = nullptr) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (out_json) a_.raw("out", out_json);
  if (where_json) a_.raw("where", where_json);
  return rt.invoke("broadcast_plus", ins_, a_.str());
}

inline std::vector<PackedTensor> broadcast_power(
    PyRuntime& rt,
    const PackedTensor& x1,
    const PackedTensor& x2) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x1);
  ins_.push_back(x2);
  detail::JsonBuilder a_;
  return rt.invoke("broadcast_power", ins_, a_.str());
}

inline std::vector<PackedTensor> broadcast_sub(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* out_json = nullptr,
    const char* where_json = nullptr) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (out_json) a_.raw("out", out_json);
  if (where_json) a_.raw("where", where_json);
  return rt.invoke("broadcast_sub", ins_, a_.str());
}

inline std::vector<PackedTensor> broadcast_to(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& shape) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(shape);
  detail::JsonBuilder a_;
  return rt.invoke("broadcast_to", ins_, a_.str());
}

inline std::vector<PackedTensor> cast(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& dtype) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(dtype);
  detail::JsonBuilder a_;
  return rt.invoke("cast", ins_, a_.str());
}

inline std::vector<PackedTensor> cast_storage(
    PyRuntime& rt,
    const PackedTensor& arr,
    const PackedTensor& stype) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(arr);
  ins_.push_back(stype);
  detail::JsonBuilder a_;
  return rt.invoke("cast_storage", ins_, a_.str());
}

inline std::vector<PackedTensor> cbrt(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("cbrt", ins_, a_.str());
}

inline std::vector<PackedTensor> ceil(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("ceil", ins_, a_.str());
}

inline std::vector<PackedTensor> choose_element_0index(
    PyRuntime& rt,
    const PackedTensor& lhs,
    const PackedTensor& rhs) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(lhs);
  ins_.push_back(rhs);
  detail::JsonBuilder a_;
  return rt.invoke("choose_element_0index", ins_, a_.str());
}

inline std::vector<PackedTensor> clip(
    PyRuntime& rt,
    const PackedTensor& data,
    const char* a_min_json = nullptr,
    const char* a_max_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (a_min_json) a_.raw("a_min", a_min_json);
  if (a_max_json) a_.raw("a_max", a_max_json);
  return rt.invoke("clip", ins_, a_.str());
}

inline std::vector<PackedTensor> col2im(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& output_size,
    const PackedTensor& kernel,
    const char* stride_json = nullptr,
    const char* dilate_json = nullptr,
    const char* pad_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(output_size);
  ins_.push_back(kernel);
  detail::JsonBuilder a_;
  if (stride_json) a_.raw("stride", stride_json);
  if (dilate_json) a_.raw("dilate", dilate_json);
  if (pad_json) a_.raw("pad", pad_json);
  return rt.invoke("col2im", ins_, a_.str());
}

inline std::vector<PackedTensor> concat(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    long long dim = 1) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  a_.put_int("dim", dim);
  return rt.invoke("concat", ins_, a_.str());
}

inline std::vector<PackedTensor> convolution(
    PyRuntime& rt,
    const PackedTensor& x,
    const PackedTensor& weight,
    const PackedTensor* bias = nullptr,
    const char* stride_json = nullptr,
    const char* pad_json = nullptr,
    const char* dilate_json = nullptr,
    long long groups = 1,
    const char* layout_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  ins_.push_back(weight);
  if (bias) ins_.push_back(*bias);
  detail::JsonBuilder a_;
  if (stride_json) a_.raw("stride", stride_json);
  if (pad_json) a_.raw("pad", pad_json);
  if (dilate_json) a_.raw("dilate", dilate_json);
  a_.put_int("groups", groups);
  if (layout_json) a_.raw("layout", layout_json);
  return rt.invoke("convolution", ins_, a_.str());
}

inline std::vector<PackedTensor> cos(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("cos", ins_, a_.str());
}

inline std::vector<PackedTensor> cosh(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("cosh", ins_, a_.str());
}

inline std::vector<PackedTensor> ctc_loss(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& label,
    const char* data_lengths_json = nullptr,
    const char* label_lengths_json = nullptr,
    bool use_data_lengths = false,
    bool use_label_lengths = false,
    const std::string& blank_label = "first") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(label);
  detail::JsonBuilder a_;
  if (data_lengths_json) a_.raw("data_lengths", data_lengths_json);
  if (label_lengths_json) a_.raw("label_lengths", label_lengths_json);
  a_.put_bool("use_data_lengths", use_data_lengths);
  a_.put_bool("use_label_lengths", use_label_lengths);
  a_.put_str("blank_label", blank_label);
  return rt.invoke("ctc_loss", ins_, a_.str());
}

inline std::vector<PackedTensor> cumsum(
    PyRuntime& rt,
    const PackedTensor& a,
    const char* axis_json = nullptr,
    const char* dtype_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("cumsum", ins_, a_.str());
}

inline std::vector<PackedTensor> deconvolution(
    PyRuntime& rt,
    const PackedTensor& x,
    const PackedTensor& weight,
    const PackedTensor* bias = nullptr,
    const char* stride_json = nullptr,
    const char* pad_json = nullptr,
    const char* dilate_json = nullptr,
    const char* output_padding_json = nullptr,
    long long groups = 1,
    const char* layout_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  ins_.push_back(weight);
  if (bias) ins_.push_back(*bias);
  detail::JsonBuilder a_;
  if (stride_json) a_.raw("stride", stride_json);
  if (pad_json) a_.raw("pad", pad_json);
  if (dilate_json) a_.raw("dilate", dilate_json);
  if (output_padding_json) a_.raw("output_padding", output_padding_json);
  a_.put_int("groups", groups);
  if (layout_json) a_.raw("layout", layout_json);
  return rt.invoke("deconvolution", ins_, a_.str());
}

inline std::vector<PackedTensor> degrees(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("degrees", ins_, a_.str());
}

inline std::vector<PackedTensor> depth_to_space(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& block_size) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(block_size);
  detail::JsonBuilder a_;
  return rt.invoke("depth_to_space", ins_, a_.str());
}

inline std::vector<PackedTensor> diag(
    PyRuntime& rt,
    const PackedTensor& data,
    long long k = 0,
    long long axis1 = 0,
    long long axis2 = 1) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  a_.put_int("k", k);
  a_.put_int("axis1", axis1);
  a_.put_int("axis2", axis2);
  return rt.invoke("diag", ins_, a_.str());
}

inline std::vector<PackedTensor> digamma(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("digamma", ins_, a_.str());
}

inline std::vector<PackedTensor> dot(
    PyRuntime& rt,
    const PackedTensor& lhs,
    const PackedTensor& rhs,
    bool transpose_a = false,
    bool transpose_b = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(lhs);
  ins_.push_back(rhs);
  detail::JsonBuilder a_;
  a_.put_bool("transpose_a", transpose_a);
  a_.put_bool("transpose_b", transpose_b);
  return rt.invoke("dot", ins_, a_.str());
}

inline std::vector<PackedTensor> dropout(
    PyRuntime& rt,
    const PackedTensor& x,
    const PackedTensor& key,
    double p = 0.5,
    bool training = true,
    const char* axes_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  ins_.push_back(key);
  detail::JsonBuilder a_;
  a_.put_num("p", p);
  a_.put_bool("training", training);
  if (axes_json) a_.raw("axes", axes_json);
  return rt.invoke("dropout", ins_, a_.str());
}

inline std::vector<PackedTensor> elemwise_add(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* out_json = nullptr,
    const char* where_json = nullptr) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (out_json) a_.raw("out", out_json);
  if (where_json) a_.raw("where", where_json);
  return rt.invoke("elemwise_add", ins_, a_.str());
}

inline std::vector<PackedTensor> elemwise_div(
    PyRuntime& rt,
    const PackedTensor& x1,
    const PackedTensor& x2) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x1);
  ins_.push_back(x2);
  detail::JsonBuilder a_;
  return rt.invoke("elemwise_div", ins_, a_.str());
}

inline std::vector<PackedTensor> elemwise_mul(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* out_json = nullptr,
    const char* where_json = nullptr) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (out_json) a_.raw("out", out_json);
  if (where_json) a_.raw("where", where_json);
  return rt.invoke("elemwise_mul", ins_, a_.str());
}

inline std::vector<PackedTensor> elemwise_sub(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* out_json = nullptr,
    const char* where_json = nullptr) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (out_json) a_.raw("out", out_json);
  if (where_json) a_.raw("where", where_json);
  return rt.invoke("elemwise_sub", ins_, a_.str());
}

inline std::vector<PackedTensor> embedding(
    PyRuntime& rt,
    const PackedTensor& indices,
    const PackedTensor& weight,
    const char* input_dim_json = nullptr,
    const char* output_dim_json = nullptr,
    const char* dtype_json = nullptr,
    bool sparse_grad = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(indices);
  ins_.push_back(weight);
  detail::JsonBuilder a_;
  if (input_dim_json) a_.raw("input_dim", input_dim_json);
  if (output_dim_json) a_.raw("output_dim", output_dim_json);
  if (dtype_json) a_.raw("dtype", dtype_json);
  a_.put_bool("sparse_grad", sparse_grad);
  return rt.invoke("embedding", ins_, a_.str());
}

inline std::vector<PackedTensor> erf(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("erf", ins_, a_.str());
}

inline std::vector<PackedTensor> erfinv(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("erfinv", ins_, a_.str());
}

inline std::vector<PackedTensor> exp(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("exp", ins_, a_.str());
}

inline std::vector<PackedTensor> expand_dims(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& axis) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(axis);
  detail::JsonBuilder a_;
  return rt.invoke("expand_dims", ins_, a_.str());
}

inline std::vector<PackedTensor> expm1(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("expm1", ins_, a_.str());
}

inline std::vector<PackedTensor> fill_element_0index(
    PyRuntime& rt,
    const PackedTensor& lhs,
    const PackedTensor& mhs,
    const PackedTensor& rhs) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(lhs);
  ins_.push_back(mhs);
  ins_.push_back(rhs);
  detail::JsonBuilder a_;
  return rt.invoke("fill_element_0index", ins_, a_.str());
}

inline std::vector<PackedTensor> fix(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("fix", ins_, a_.str());
}

inline std::vector<PackedTensor> flash_attention(
    PyRuntime& rt,
    const PackedTensor& q,
    const PackedTensor& k,
    const PackedTensor& v,
    bool causal = false,
    const char* scale_json = nullptr,
    const char* block_q_json = nullptr,
    const char* block_k_json = nullptr,
    const char* interpret_json = nullptr,
    double dropout_p = 0.0,
    const char* dropout_seed_json = nullptr,
    const char* block_diffusion_json = nullptr,
    const char* window_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(q);
  ins_.push_back(k);
  ins_.push_back(v);
  detail::JsonBuilder a_;
  a_.put_bool("causal", causal);
  if (scale_json) a_.raw("scale", scale_json);
  if (block_q_json) a_.raw("block_q", block_q_json);
  if (block_k_json) a_.raw("block_k", block_k_json);
  if (interpret_json) a_.raw("interpret", interpret_json);
  a_.put_num("dropout_p", dropout_p);
  if (dropout_seed_json) a_.raw("dropout_seed", dropout_seed_json);
  if (block_diffusion_json) a_.raw("block_diffusion", block_diffusion_json);
  if (window_json) a_.raw("window", window_json);
  return rt.invoke("flash_attention", ins_, a_.str());
}

inline std::vector<PackedTensor> flatten(
    PyRuntime& rt,
    const PackedTensor& data) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  return rt.invoke("flatten", ins_, a_.str());
}

inline std::vector<PackedTensor> flip(
    PyRuntime& rt,
    const PackedTensor& data,
    long long axis = 0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  a_.put_int("axis", axis);
  return rt.invoke("flip", ins_, a_.str());
}

inline std::vector<PackedTensor> floor(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("floor", ins_, a_.str());
}

inline std::vector<PackedTensor> ftml_update(
    PyRuntime& rt,
    const PackedTensor& weight,
    const PackedTensor& grad,
    const PackedTensor& d,
    const PackedTensor& v,
    const PackedTensor& z,
    const PackedTensor& lr,
    const PackedTensor& t,
    double beta1 = 0.6,
    double beta2 = 0.999,
    double epsilon = 1e-08,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_grad = -1.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(weight);
  ins_.push_back(grad);
  ins_.push_back(d);
  ins_.push_back(v);
  ins_.push_back(z);
  ins_.push_back(lr);
  ins_.push_back(t);
  detail::JsonBuilder a_;
  a_.put_num("beta1", beta1);
  a_.put_num("beta2", beta2);
  a_.put_num("epsilon", epsilon);
  a_.put_num("wd", wd);
  a_.put_num("rescale_grad", rescale_grad);
  a_.put_num("clip_grad", clip_grad);
  return rt.invoke("ftml_update", ins_, a_.str());
}

inline std::vector<PackedTensor> ftrl_update(
    PyRuntime& rt,
    const PackedTensor& weight,
    const PackedTensor& grad,
    const PackedTensor& z,
    const PackedTensor& n,
    const PackedTensor& lr,
    double lamda1 = 0.01,
    double beta = 1.0,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(weight);
  ins_.push_back(grad);
  ins_.push_back(z);
  ins_.push_back(n);
  ins_.push_back(lr);
  detail::JsonBuilder a_;
  a_.put_num("lamda1", lamda1);
  a_.put_num("beta", beta);
  a_.put_num("wd", wd);
  a_.put_num("rescale_grad", rescale_grad);
  a_.put_num("clip_gradient", clip_gradient);
  return rt.invoke("ftrl_update", ins_, a_.str());
}

inline std::vector<PackedTensor> fully_connected(
    PyRuntime& rt,
    const PackedTensor& x,
    const PackedTensor& weight,
    const PackedTensor* bias = nullptr,
    bool flatten = true,
    const char* num_hidden_json = nullptr,
    const char* no_bias_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  ins_.push_back(weight);
  if (bias) ins_.push_back(*bias);
  detail::JsonBuilder a_;
  a_.put_bool("flatten", flatten);
  if (num_hidden_json) a_.raw("num_hidden", num_hidden_json);
  if (no_bias_json) a_.raw("no_bias", no_bias_json);
  return rt.invoke("fully_connected", ins_, a_.str());
}

inline std::vector<PackedTensor> gamma(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("gamma", ins_, a_.str());
}

inline std::vector<PackedTensor> gammaln(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("gammaln", ins_, a_.str());
}

inline std::vector<PackedTensor> gated_short_conv(
    PyRuntime& rt,
    const PackedTensor& bcx,
    const PackedTensor& w) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(bcx);
  ins_.push_back(w);
  detail::JsonBuilder a_;
  return rt.invoke("gated_short_conv", ins_, a_.str());
}

inline std::vector<PackedTensor> gather_nd(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& indices) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(indices);
  detail::JsonBuilder a_;
  return rt.invoke("gather_nd", ins_, a_.str());
}

inline std::vector<PackedTensor> group_adagrad_update(
    PyRuntime& rt,
    const PackedTensor& weight,
    const PackedTensor& grad,
    const PackedTensor& history,
    const PackedTensor& lr,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    double epsilon = 1e-05) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(weight);
  ins_.push_back(grad);
  ins_.push_back(history);
  ins_.push_back(lr);
  detail::JsonBuilder a_;
  a_.put_num("rescale_grad", rescale_grad);
  a_.put_num("clip_gradient", clip_gradient);
  a_.put_num("epsilon", epsilon);
  return rt.invoke("group_adagrad_update", ins_, a_.str());
}

inline std::vector<PackedTensor> group_norm(
    PyRuntime& rt,
    const PackedTensor& x,
    const PackedTensor& gamma,
    const PackedTensor& beta,
    const PackedTensor& num_groups,
    double eps = 1e-05) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  ins_.push_back(gamma);
  ins_.push_back(beta);
  ins_.push_back(num_groups);
  detail::JsonBuilder a_;
  a_.put_num("eps", eps);
  return rt.invoke("group_norm", ins_, a_.str());
}

inline std::vector<PackedTensor> hard_sigmoid(
    PyRuntime& rt,
    const PackedTensor& x,
    double alpha = 0.2,
    double beta = 0.5) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  a_.put_num("alpha", alpha);
  a_.put_num("beta", beta);
  return rt.invoke("hard_sigmoid", ins_, a_.str());
}

inline std::vector<PackedTensor> histogram(
    PyRuntime& rt,
    const PackedTensor& data,
    const char* bins_json = nullptr,
    const char* bin_cnt_json = nullptr,
    const char* range_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (bins_json) a_.raw("bins", bins_json);
  if (bin_cnt_json) a_.raw("bin_cnt", bin_cnt_json);
  if (range_json) a_.raw("range", range_json);
  return rt.invoke("histogram", ins_, a_.str());
}

inline std::vector<PackedTensor> hypot(
    PyRuntime& rt,
    const PackedTensor& x1,
    const PackedTensor& x2) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x1);
  ins_.push_back(x2);
  detail::JsonBuilder a_;
  return rt.invoke("hypot", ins_, a_.str());
}

inline std::vector<PackedTensor> identity(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("identity", ins_, a_.str());
}

inline std::vector<PackedTensor> im2col(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& kernel,
    const char* stride_json = nullptr,
    const char* dilate_json = nullptr,
    const char* pad_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(kernel);
  detail::JsonBuilder a_;
  if (stride_json) a_.raw("stride", stride_json);
  if (dilate_json) a_.raw("dilate", dilate_json);
  if (pad_json) a_.raw("pad", pad_json);
  return rt.invoke("im2col", ins_, a_.str());
}

inline std::vector<PackedTensor> instance_norm(
    PyRuntime& rt,
    const PackedTensor& x,
    const PackedTensor& gamma,
    const PackedTensor& beta,
    double eps = 1e-05) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  ins_.push_back(gamma);
  ins_.push_back(beta);
  detail::JsonBuilder a_;
  a_.put_num("eps", eps);
  return rt.invoke("instance_norm", ins_, a_.str());
}

inline std::vector<PackedTensor> kda_scan(
    PyRuntime& rt,
    const PackedTensor& q,
    const PackedTensor& k,
    const PackedTensor& v,
    const PackedTensor& g,
    const PackedTensor& beta,
    const char* scale_json = nullptr,
    long long chunk = 64) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(q);
  ins_.push_back(k);
  ins_.push_back(v);
  ins_.push_back(g);
  ins_.push_back(beta);
  detail::JsonBuilder a_;
  if (scale_json) a_.raw("scale", scale_json);
  a_.put_int("chunk", chunk);
  return rt.invoke("kda_scan", ins_, a_.str());
}

inline std::vector<PackedTensor> khatri_rao(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  return rt.invoke("khatri_rao", ins_, a_.str());
}

inline std::vector<PackedTensor> l2_normalization(
    PyRuntime& rt,
    const PackedTensor& x,
    double eps = 1e-10,
    const std::string& mode = "instance") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  a_.put_num("eps", eps);
  a_.put_str("mode", mode);
  return rt.invoke("l2_normalization", ins_, a_.str());
}

inline std::vector<PackedTensor> lamb_update_phase1(
    PyRuntime& rt,
    const PackedTensor& weight,
    const PackedTensor& grad,
    const PackedTensor& mean,
    const PackedTensor& var,
    double beta1 = 0.9,
    double beta2 = 0.999,
    double epsilon = 1e-06,
    long long t = 1,
    bool bias_correction = true,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(weight);
  ins_.push_back(grad);
  ins_.push_back(mean);
  ins_.push_back(var);
  detail::JsonBuilder a_;
  a_.put_num("beta1", beta1);
  a_.put_num("beta2", beta2);
  a_.put_num("epsilon", epsilon);
  a_.put_int("t", t);
  a_.put_bool("bias_correction", bias_correction);
  a_.put_num("wd", wd);
  a_.put_num("rescale_grad", rescale_grad);
  a_.put_num("clip_gradient", clip_gradient);
  return rt.invoke("lamb_update_phase1", ins_, a_.str());
}

inline std::vector<PackedTensor> lamb_update_phase2(
    PyRuntime& rt,
    const PackedTensor& weight,
    const PackedTensor& g,
    const PackedTensor& r1,
    const PackedTensor& r2,
    const PackedTensor& lr,
    double lower_bound = -1.0,
    double upper_bound = -1.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(weight);
  ins_.push_back(g);
  ins_.push_back(r1);
  ins_.push_back(r2);
  ins_.push_back(lr);
  detail::JsonBuilder a_;
  a_.put_num("lower_bound", lower_bound);
  a_.put_num("upper_bound", upper_bound);
  return rt.invoke("lamb_update_phase2", ins_, a_.str());
}

inline std::vector<PackedTensor> lans_update_phase1(
    PyRuntime& rt,
    const PackedTensor& weight,
    const PackedTensor& grad,
    const PackedTensor& mean,
    const PackedTensor& var,
    double beta1 = 0.9,
    double beta2 = 0.999,
    double epsilon = 1e-06,
    long long t = 1,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(weight);
  ins_.push_back(grad);
  ins_.push_back(mean);
  ins_.push_back(var);
  detail::JsonBuilder a_;
  a_.put_num("beta1", beta1);
  a_.put_num("beta2", beta2);
  a_.put_num("epsilon", epsilon);
  a_.put_int("t", t);
  a_.put_num("wd", wd);
  a_.put_num("rescale_grad", rescale_grad);
  a_.put_num("clip_gradient", clip_gradient);
  return rt.invoke("lans_update_phase1", ins_, a_.str());
}

inline std::vector<PackedTensor> layer_norm(
    PyRuntime& rt,
    const PackedTensor& x,
    const PackedTensor& gamma,
    const PackedTensor& beta,
    long long axis = -1,
    double eps = 1e-05) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  ins_.push_back(gamma);
  ins_.push_back(beta);
  detail::JsonBuilder a_;
  a_.put_int("axis", axis);
  a_.put_num("eps", eps);
  return rt.invoke("layer_norm", ins_, a_.str());
}

inline std::vector<PackedTensor> leaky_relu(
    PyRuntime& rt,
    const PackedTensor& x,
    const PackedTensor* gamma = nullptr,
    const std::string& act_type = "leaky",
    double slope = 0.25) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  if (gamma) ins_.push_back(*gamma);
  detail::JsonBuilder a_;
  a_.put_str("act_type", act_type);
  a_.put_num("slope", slope);
  return rt.invoke("leaky_relu", ins_, a_.str());
}

inline std::vector<PackedTensor> linalg_cholesky(
    PyRuntime& rt,
    const PackedTensor& A,
    bool lower = true) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  a_.put_bool("lower", lower);
  return rt.invoke("linalg_cholesky", ins_, a_.str());
}

inline std::vector<PackedTensor> linalg_det(
    PyRuntime& rt,
    const PackedTensor& A) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  return rt.invoke("linalg_det", ins_, a_.str());
}

inline std::vector<PackedTensor> linalg_eig(
    PyRuntime& rt,
    const PackedTensor& A) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  return rt.invoke("linalg_eig", ins_, a_.str());
}

inline std::vector<PackedTensor> linalg_eigh(
    PyRuntime& rt,
    const PackedTensor& A,
    bool upper = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  a_.put_bool("upper", upper);
  return rt.invoke("linalg_eigh", ins_, a_.str());
}

inline std::vector<PackedTensor> linalg_eigvals(
    PyRuntime& rt,
    const PackedTensor& A) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  return rt.invoke("linalg_eigvals", ins_, a_.str());
}

inline std::vector<PackedTensor> linalg_eigvalsh(
    PyRuntime& rt,
    const PackedTensor& A) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  return rt.invoke("linalg_eigvalsh", ins_, a_.str());
}

inline std::vector<PackedTensor> linalg_extractdiag(
    PyRuntime& rt,
    const PackedTensor& A,
    long long offset = 0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  a_.put_int("offset", offset);
  return rt.invoke("linalg_extractdiag", ins_, a_.str());
}

inline std::vector<PackedTensor> linalg_extracttrian(
    PyRuntime& rt,
    const PackedTensor& A,
    long long offset = 0,
    bool lower = true) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  a_.put_int("offset", offset);
  a_.put_bool("lower", lower);
  return rt.invoke("linalg_extracttrian", ins_, a_.str());
}

inline std::vector<PackedTensor> linalg_gelqf(
    PyRuntime& rt,
    const PackedTensor& A) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  return rt.invoke("linalg_gelqf", ins_, a_.str());
}

inline std::vector<PackedTensor> linalg_gemm(
    PyRuntime& rt,
    const PackedTensor& A,
    const PackedTensor& B,
    const PackedTensor& C,
    bool transpose_a = false,
    bool transpose_b = false,
    double alpha = 1.0,
    double beta = 1.0,
    long long axis = -2) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  ins_.push_back(B);
  ins_.push_back(C);
  detail::JsonBuilder a_;
  a_.put_bool("transpose_a", transpose_a);
  a_.put_bool("transpose_b", transpose_b);
  a_.put_num("alpha", alpha);
  a_.put_num("beta", beta);
  a_.put_int("axis", axis);
  return rt.invoke("linalg_gemm", ins_, a_.str());
}

inline std::vector<PackedTensor> linalg_gemm2(
    PyRuntime& rt,
    const PackedTensor& A,
    const PackedTensor& B,
    bool transpose_a = false,
    bool transpose_b = false,
    double alpha = 1.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  ins_.push_back(B);
  detail::JsonBuilder a_;
  a_.put_bool("transpose_a", transpose_a);
  a_.put_bool("transpose_b", transpose_b);
  a_.put_num("alpha", alpha);
  return rt.invoke("linalg_gemm2", ins_, a_.str());
}

inline std::vector<PackedTensor> linalg_inverse(
    PyRuntime& rt,
    const PackedTensor& A) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  return rt.invoke("linalg_inverse", ins_, a_.str());
}

inline std::vector<PackedTensor> linalg_kron(
    PyRuntime& rt,
    const PackedTensor& a,
    const PackedTensor& b) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  ins_.push_back(b);
  detail::JsonBuilder a_;
  return rt.invoke("linalg_kron", ins_, a_.str());
}

inline std::vector<PackedTensor> linalg_lstsq(
    PyRuntime& rt,
    const PackedTensor& A,
    const PackedTensor& B,
    const char* rcond_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  ins_.push_back(B);
  detail::JsonBuilder a_;
  if (rcond_json) a_.raw("rcond", rcond_json);
  return rt.invoke("linalg_lstsq", ins_, a_.str());
}

inline std::vector<PackedTensor> linalg_makediag(
    PyRuntime& rt,
    const PackedTensor& A,
    long long offset = 0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  a_.put_int("offset", offset);
  return rt.invoke("linalg_makediag", ins_, a_.str());
}

inline std::vector<PackedTensor> linalg_maketrian(
    PyRuntime& rt,
    const PackedTensor& A,
    long long offset = 0,
    bool lower = true) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  a_.put_int("offset", offset);
  a_.put_bool("lower", lower);
  return rt.invoke("linalg_maketrian", ins_, a_.str());
}

inline std::vector<PackedTensor> linalg_matmul(
    PyRuntime& rt,
    const PackedTensor& a,
    const PackedTensor& b) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  ins_.push_back(b);
  detail::JsonBuilder a_;
  return rt.invoke("linalg_matmul", ins_, a_.str());
}

inline std::vector<PackedTensor> linalg_matrix_power(
    PyRuntime& rt,
    const PackedTensor& A,
    const PackedTensor& n) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  ins_.push_back(n);
  detail::JsonBuilder a_;
  return rt.invoke("linalg_matrix_power", ins_, a_.str());
}

inline std::vector<PackedTensor> linalg_matrix_rank(
    PyRuntime& rt,
    const PackedTensor& A,
    const char* tol_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  if (tol_json) a_.raw("tol", tol_json);
  return rt.invoke("linalg_matrix_rank", ins_, a_.str());
}

inline std::vector<PackedTensor> linalg_multi_dot(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  return rt.invoke("linalg_multi_dot", ins_, a_.str());
}

inline std::vector<PackedTensor> linalg_norm(
    PyRuntime& rt,
    const PackedTensor& A,
    const char* ord_json = nullptr,
    const char* axis_json = nullptr,
    bool keepdims = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  if (ord_json) a_.raw("ord", ord_json);
  if (axis_json) a_.raw("axis", axis_json);
  a_.put_bool("keepdims", keepdims);
  return rt.invoke("linalg_norm", ins_, a_.str());
}

inline std::vector<PackedTensor> linalg_pinv(
    PyRuntime& rt,
    const PackedTensor& A,
    const char* rcond_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  if (rcond_json) a_.raw("rcond", rcond_json);
  return rt.invoke("linalg_pinv", ins_, a_.str());
}

inline std::vector<PackedTensor> linalg_potrf(
    PyRuntime& rt,
    const PackedTensor& A) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  return rt.invoke("linalg_potrf", ins_, a_.str());
}

inline std::vector<PackedTensor> linalg_potri(
    PyRuntime& rt,
    const PackedTensor& A) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  return rt.invoke("linalg_potri", ins_, a_.str());
}

inline std::vector<PackedTensor> linalg_qr(
    PyRuntime& rt,
    const PackedTensor& A) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  return rt.invoke("linalg_qr", ins_, a_.str());
}

inline std::vector<PackedTensor> linalg_slogdet(
    PyRuntime& rt,
    const PackedTensor& A) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  return rt.invoke("linalg_slogdet", ins_, a_.str());
}

inline std::vector<PackedTensor> linalg_solve(
    PyRuntime& rt,
    const PackedTensor& A,
    const PackedTensor& B) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  ins_.push_back(B);
  detail::JsonBuilder a_;
  return rt.invoke("linalg_solve", ins_, a_.str());
}

inline std::vector<PackedTensor> linalg_sumlogdiag(
    PyRuntime& rt,
    const PackedTensor& A) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  return rt.invoke("linalg_sumlogdiag", ins_, a_.str());
}

inline std::vector<PackedTensor> linalg_svd(
    PyRuntime& rt,
    const PackedTensor& A) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  return rt.invoke("linalg_svd", ins_, a_.str());
}

inline std::vector<PackedTensor> linalg_syevd(
    PyRuntime& rt,
    const PackedTensor& A) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  return rt.invoke("linalg_syevd", ins_, a_.str());
}

inline std::vector<PackedTensor> linalg_syrk(
    PyRuntime& rt,
    const PackedTensor& A,
    bool transpose = false,
    double alpha = 1.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  a_.put_bool("transpose", transpose);
  a_.put_num("alpha", alpha);
  return rt.invoke("linalg_syrk", ins_, a_.str());
}

inline std::vector<PackedTensor> linalg_tensorinv(
    PyRuntime& rt,
    const PackedTensor& A,
    long long ind = 2) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  detail::JsonBuilder a_;
  a_.put_int("ind", ind);
  return rt.invoke("linalg_tensorinv", ins_, a_.str());
}

inline std::vector<PackedTensor> linalg_tensorsolve(
    PyRuntime& rt,
    const PackedTensor& A,
    const PackedTensor& B,
    const char* axes_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  ins_.push_back(B);
  detail::JsonBuilder a_;
  if (axes_json) a_.raw("axes", axes_json);
  return rt.invoke("linalg_tensorsolve", ins_, a_.str());
}

inline std::vector<PackedTensor> linalg_trmm(
    PyRuntime& rt,
    const PackedTensor& A,
    const PackedTensor& B,
    bool transpose = false,
    bool rightside = false,
    bool lower = true,
    double alpha = 1.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  ins_.push_back(B);
  detail::JsonBuilder a_;
  a_.put_bool("transpose", transpose);
  a_.put_bool("rightside", rightside);
  a_.put_bool("lower", lower);
  a_.put_num("alpha", alpha);
  return rt.invoke("linalg_trmm", ins_, a_.str());
}

inline std::vector<PackedTensor> linalg_trsm(
    PyRuntime& rt,
    const PackedTensor& A,
    const PackedTensor& B,
    bool transpose = false,
    bool rightside = false,
    bool lower = true,
    double alpha = 1.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(A);
  ins_.push_back(B);
  detail::JsonBuilder a_;
  a_.put_bool("transpose", transpose);
  a_.put_bool("rightside", rightside);
  a_.put_bool("lower", lower);
  a_.put_num("alpha", alpha);
  return rt.invoke("linalg_trsm", ins_, a_.str());
}

inline std::vector<PackedTensor> log(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("log", ins_, a_.str());
}

inline std::vector<PackedTensor> log10(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("log10", ins_, a_.str());
}

inline std::vector<PackedTensor> log1p(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("log1p", ins_, a_.str());
}

inline std::vector<PackedTensor> log2(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("log2", ins_, a_.str());
}

inline std::vector<PackedTensor> log_sigmoid(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("log_sigmoid", ins_, a_.str());
}

inline std::vector<PackedTensor> log_softmax(
    PyRuntime& rt,
    const PackedTensor& x,
    long long axis = -1,
    const char* temperature_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  a_.put_int("axis", axis);
  if (temperature_json) a_.raw("temperature", temperature_json);
  return rt.invoke("log_softmax", ins_, a_.str());
}

inline std::vector<PackedTensor> logical_not(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("logical_not", ins_, a_.str());
}

inline std::vector<PackedTensor> lrn(
    PyRuntime& rt,
    const PackedTensor& x,
    long long nsize = 5,
    double alpha = 0.0001,
    double beta = 0.75,
    double knorm = 2.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  a_.put_int("nsize", nsize);
  a_.put_num("alpha", alpha);
  a_.put_num("beta", beta);
  a_.put_num("knorm", knorm);
  return rt.invoke("lrn", ins_, a_.str());
}

inline std::vector<PackedTensor> make_loss(
    PyRuntime& rt,
    const PackedTensor& data,
    double grad_scale = 1.0,
    double valid_thresh = 0.0,
    const std::string& normalization = "null") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  a_.put_num("grad_scale", grad_scale);
  a_.put_num("valid_thresh", valid_thresh);
  a_.put_str("normalization", normalization);
  return rt.invoke("make_loss", ins_, a_.str());
}

inline std::vector<PackedTensor> masked_log_softmax(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& mask,
    long long axis = -1,
    double temperature = 1.0,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(mask);
  detail::JsonBuilder a_;
  a_.put_int("axis", axis);
  a_.put_num("temperature", temperature);
  return rt.invoke("masked_log_softmax", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> masked_softmax(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& mask,
    long long axis = -1,
    double temperature = 1.0,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(mask);
  detail::JsonBuilder a_;
  a_.put_int("axis", axis);
  a_.put_num("temperature", temperature);
  return rt.invoke("masked_softmax", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> max(
    PyRuntime& rt,
    const PackedTensor& data,
    const char* axis_json = nullptr,
    bool keepdims = false,
    bool exclude = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  a_.put_bool("keepdims", keepdims);
  a_.put_bool("exclude", exclude);
  return rt.invoke("max", ins_, a_.str());
}

inline std::vector<PackedTensor> max_axis(
    PyRuntime& rt,
    const PackedTensor& data,
    const char* axis_json = nullptr,
    bool keepdims = false,
    bool exclude = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  a_.put_bool("keepdims", keepdims);
  a_.put_bool("exclude", exclude);
  return rt.invoke("max_axis", ins_, a_.str());
}

inline std::vector<PackedTensor> mean(
    PyRuntime& rt,
    const PackedTensor& data,
    const char* axis_json = nullptr,
    bool keepdims = false,
    bool exclude = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  a_.put_bool("keepdims", keepdims);
  a_.put_bool("exclude", exclude);
  return rt.invoke("mean", ins_, a_.str());
}

inline std::vector<PackedTensor> min(
    PyRuntime& rt,
    const PackedTensor& data,
    const char* axis_json = nullptr,
    bool keepdims = false,
    bool exclude = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  a_.put_bool("keepdims", keepdims);
  a_.put_bool("exclude", exclude);
  return rt.invoke("min", ins_, a_.str());
}

inline std::vector<PackedTensor> min_axis(
    PyRuntime& rt,
    const PackedTensor& data,
    const char* axis_json = nullptr,
    bool keepdims = false,
    bool exclude = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  a_.put_bool("keepdims", keepdims);
  a_.put_bool("exclude", exclude);
  return rt.invoke("min_axis", ins_, a_.str());
}

inline std::vector<PackedTensor> mish(
    PyRuntime& rt,
    const PackedTensor& data) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  return rt.invoke("mish", ins_, a_.str());
}

inline std::vector<PackedTensor> mla_heads(
    PyRuntime& rt,
    const PackedTensor& q,
    const PackedTensor& kv,
    const PackedTensor& k_rope,
    const PackedTensor& positions,
    double theta = 10000.0,
    long long num_heads = 1,
    bool interleaved = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(q);
  ins_.push_back(kv);
  ins_.push_back(k_rope);
  ins_.push_back(positions);
  detail::JsonBuilder a_;
  a_.put_num("theta", theta);
  a_.put_int("num_heads", num_heads);
  a_.put_bool("interleaved", interleaved);
  return rt.invoke("mla_heads", ins_, a_.str());
}

inline std::vector<PackedTensor> moments(
    PyRuntime& rt,
    const PackedTensor& x,
    const char* axes_json = nullptr,
    bool keepdims = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  if (axes_json) a_.raw("axes", axes_json);
  a_.put_bool("keepdims", keepdims);
  return rt.invoke("moments", ins_, a_.str());
}

inline std::vector<PackedTensor> mp_adabelief_update(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& weight,
    const PackedTensor& grad,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(weight);
  ins_.push_back(grad);
  detail::JsonBuilder a_;
  return rt.invoke("mp_adabelief_update", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> mp_adamw_update(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& weight,
    const PackedTensor& grad,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(weight);
  ins_.push_back(grad);
  detail::JsonBuilder a_;
  return rt.invoke("mp_adamw_update", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> mp_lamb_update_phase1(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& weight,
    const PackedTensor& grad,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(weight);
  ins_.push_back(grad);
  detail::JsonBuilder a_;
  return rt.invoke("mp_lamb_update_phase1", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> mp_lamb_update_phase2(
    PyRuntime& rt,
    const PackedTensor& weight,
    const PackedTensor& g,
    const PackedTensor& r1,
    const PackedTensor& r2,
    const PackedTensor& lr,
    double lower_bound = -1.0,
    double upper_bound = -1.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(weight);
  ins_.push_back(g);
  ins_.push_back(r1);
  ins_.push_back(r2);
  ins_.push_back(lr);
  detail::JsonBuilder a_;
  a_.put_num("lower_bound", lower_bound);
  a_.put_num("upper_bound", upper_bound);
  return rt.invoke("mp_lamb_update_phase2", ins_, a_.str());
}

inline std::vector<PackedTensor> mp_nag_mom_update(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& weight,
    const PackedTensor& grad,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(weight);
  ins_.push_back(grad);
  detail::JsonBuilder a_;
  return rt.invoke("mp_nag_mom_update", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> mp_sgd_mom_update(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& weight,
    const PackedTensor& grad,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(weight);
  ins_.push_back(grad);
  detail::JsonBuilder a_;
  return rt.invoke("mp_sgd_mom_update", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> mp_sgd_update(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const PackedTensor& weight,
    const PackedTensor& grad,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  ins_.push_back(weight);
  ins_.push_back(grad);
  detail::JsonBuilder a_;
  return rt.invoke("mp_sgd_update", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> multi_all_finite(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* num_arrays_json = nullptr,
    bool init_output = true) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (num_arrays_json) a_.raw("num_arrays", num_arrays_json);
  a_.put_bool("init_output", init_output);
  return rt.invoke("multi_all_finite", ins_, a_.str());
}

inline std::vector<PackedTensor> multi_lars(
    PyRuntime& rt,
    const PackedTensor& lrs,
    const PackedTensor& weights_sum_sq,
    const PackedTensor& grads_sum_sq,
    const PackedTensor& wds,
    double eta = 0.001,
    double eps = 1e-08,
    double rescale_grad = 1.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(lrs);
  ins_.push_back(weights_sum_sq);
  ins_.push_back(grads_sum_sq);
  ins_.push_back(wds);
  detail::JsonBuilder a_;
  a_.put_num("eta", eta);
  a_.put_num("eps", eps);
  a_.put_num("rescale_grad", rescale_grad);
  return rt.invoke("multi_lars", ins_, a_.str());
}

inline std::vector<PackedTensor> multi_mp_sgd_mom_update(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* num_weights_json = nullptr,
    const char* lrs_json = nullptr,
    const char* wds_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (num_weights_json) a_.raw("num_weights", num_weights_json);
  if (lrs_json) a_.raw("lrs", lrs_json);
  if (wds_json) a_.raw("wds", wds_json);
  return rt.invoke("multi_mp_sgd_mom_update", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> multi_mp_sgd_update(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* num_weights_json = nullptr,
    const char* lrs_json = nullptr,
    const char* wds_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (num_weights_json) a_.raw("num_weights", num_weights_json);
  if (lrs_json) a_.raw("lrs", lrs_json);
  if (wds_json) a_.raw("wds", wds_json);
  return rt.invoke("multi_mp_sgd_update", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> multi_sgd_mom_update(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* num_weights_json = nullptr,
    const char* lrs_json = nullptr,
    const char* wds_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (num_weights_json) a_.raw("num_weights", num_weights_json);
  if (lrs_json) a_.raw("lrs", lrs_json);
  if (wds_json) a_.raw("wds", wds_json);
  return rt.invoke("multi_sgd_mom_update", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> multi_sgd_update(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* num_weights_json = nullptr,
    const char* lrs_json = nullptr,
    const char* wds_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (num_weights_json) a_.raw("num_weights", num_weights_json);
  if (lrs_json) a_.raw("lrs", lrs_json);
  if (wds_json) a_.raw("wds", wds_json);
  return rt.invoke("multi_sgd_update", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> multi_sum_sq(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* num_arrays_json = nullptr) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (num_arrays_json) a_.raw("num_arrays", num_arrays_json);
  return rt.invoke("multi_sum_sq", ins_, a_.str());
}

inline std::vector<PackedTensor> nag_mom_update(
    PyRuntime& rt,
    const PackedTensor& weight,
    const PackedTensor& grad,
    const PackedTensor& mom,
    const PackedTensor& lr,
    double momentum = 0.0,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(weight);
  ins_.push_back(grad);
  ins_.push_back(mom);
  ins_.push_back(lr);
  detail::JsonBuilder a_;
  a_.put_num("momentum", momentum);
  a_.put_num("wd", wd);
  a_.put_num("rescale_grad", rescale_grad);
  a_.put_num("clip_gradient", clip_gradient);
  return rt.invoke("nag_mom_update", ins_, a_.str());
}

inline std::vector<PackedTensor> nanprod(
    PyRuntime& rt,
    const PackedTensor& data,
    const char* axis_json = nullptr,
    bool keepdims = false,
    bool exclude = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  a_.put_bool("keepdims", keepdims);
  a_.put_bool("exclude", exclude);
  return rt.invoke("nanprod", ins_, a_.str());
}

inline std::vector<PackedTensor> nansum(
    PyRuntime& rt,
    const PackedTensor& data,
    const char* axis_json = nullptr,
    bool keepdims = false,
    bool exclude = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  a_.put_bool("keepdims", keepdims);
  a_.put_bool("exclude", exclude);
  return rt.invoke("nansum", ins_, a_.str());
}

inline std::vector<PackedTensor> negative(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* out_json = nullptr,
    const char* where_json = nullptr) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (out_json) a_.raw("out", out_json);
  if (where_json) a_.raw("where", where_json);
  return rt.invoke("negative", ins_, a_.str());
}

inline std::vector<PackedTensor> norm(
    PyRuntime& rt,
    const PackedTensor& data,
    long long ord = 2,
    const char* axis_json = nullptr,
    bool keepdims = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  a_.put_int("ord", ord);
  if (axis_json) a_.raw("axis", axis_json);
  a_.put_bool("keepdims", keepdims);
  return rt.invoke("norm", ins_, a_.str());
}

inline std::vector<PackedTensor> one_hot(
    PyRuntime& rt,
    const PackedTensor& indices,
    const PackedTensor& depth,
    double on_value = 1.0,
    double off_value = 0.0,
    const char* dtype_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(indices);
  ins_.push_back(depth);
  detail::JsonBuilder a_;
  a_.put_num("on_value", on_value);
  a_.put_num("off_value", off_value);
  if (dtype_json) a_.raw("dtype", dtype_json);
  return rt.invoke("one_hot", ins_, a_.str());
}

inline std::vector<PackedTensor> pad(
    PyRuntime& rt,
    const PackedTensor& data,
    const std::string& mode = "constant",
    const char* pad_width_json = nullptr,
    double constant_value = 0.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  a_.put_str("mode", mode);
  if (pad_width_json) a_.raw("pad_width", pad_width_json);
  a_.put_num("constant_value", constant_value);
  return rt.invoke("pad", ins_, a_.str());
}

inline std::vector<PackedTensor> pick(
    PyRuntime& rt,
    const PackedTensor& x,
    const PackedTensor& index,
    long long axis = -1,
    bool keepdims = false,
    const std::string& mode = "clip") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  ins_.push_back(index);
  detail::JsonBuilder a_;
  a_.put_int("axis", axis);
  a_.put_bool("keepdims", keepdims);
  a_.put_str("mode", mode);
  return rt.invoke("pick", ins_, a_.str());
}

inline std::vector<PackedTensor> pooling(
    PyRuntime& rt,
    const PackedTensor& x,
    const PackedTensor& kernel,
    const std::string& pool_type = "max",
    const char* stride_json = nullptr,
    const char* pad_json = nullptr,
    bool global_pool = false,
    bool count_include_pad = true,
    const char* layout_json = nullptr,
    bool ceil_mode = false,
    const char* pooling_convention_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  ins_.push_back(kernel);
  detail::JsonBuilder a_;
  a_.put_str("pool_type", pool_type);
  if (stride_json) a_.raw("stride", stride_json);
  if (pad_json) a_.raw("pad", pad_json);
  a_.put_bool("global_pool", global_pool);
  a_.put_bool("count_include_pad", count_include_pad);
  if (layout_json) a_.raw("layout", layout_json);
  a_.put_bool("ceil_mode", ceil_mode);
  if (pooling_convention_json) a_.raw("pooling_convention", pooling_convention_json);
  return rt.invoke("pooling", ins_, a_.str());
}

inline std::vector<PackedTensor> preloaded_multi_mp_sgd_mom_update(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* num_weights_json = nullptr,
    const char* lrs_json = nullptr,
    const char* wds_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (num_weights_json) a_.raw("num_weights", num_weights_json);
  if (lrs_json) a_.raw("lrs", lrs_json);
  if (wds_json) a_.raw("wds", wds_json);
  return rt.invoke("preloaded_multi_mp_sgd_mom_update", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> preloaded_multi_mp_sgd_update(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* num_weights_json = nullptr,
    const char* lrs_json = nullptr,
    const char* wds_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (num_weights_json) a_.raw("num_weights", num_weights_json);
  if (lrs_json) a_.raw("lrs", lrs_json);
  if (wds_json) a_.raw("wds", wds_json);
  return rt.invoke("preloaded_multi_mp_sgd_update", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> preloaded_multi_sgd_mom_update(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* num_weights_json = nullptr,
    const char* lrs_json = nullptr,
    const char* wds_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (num_weights_json) a_.raw("num_weights", num_weights_json);
  if (lrs_json) a_.raw("lrs", lrs_json);
  if (wds_json) a_.raw("wds", wds_json);
  return rt.invoke("preloaded_multi_sgd_mom_update", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> preloaded_multi_sgd_update(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* num_weights_json = nullptr,
    const char* lrs_json = nullptr,
    const char* wds_json = nullptr,
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (num_weights_json) a_.raw("num_weights", num_weights_json);
  if (lrs_json) a_.raw("lrs", lrs_json);
  if (wds_json) a_.raw("wds", wds_json);
  return rt.invoke("preloaded_multi_sgd_update", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> prod(
    PyRuntime& rt,
    const PackedTensor& data,
    const char* axis_json = nullptr,
    bool keepdims = false,
    bool exclude = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  a_.put_bool("keepdims", keepdims);
  a_.put_bool("exclude", exclude);
  return rt.invoke("prod", ins_, a_.str());
}

inline std::vector<PackedTensor> radians(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("radians", ins_, a_.str());
}

inline std::vector<PackedTensor> ravel_multi_index(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& shape) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(shape);
  detail::JsonBuilder a_;
  return rt.invoke("ravel_multi_index", ins_, a_.str());
}

inline std::vector<PackedTensor> rcbrt(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("rcbrt", ins_, a_.str());
}

inline std::vector<PackedTensor> reciprocal(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("reciprocal", ins_, a_.str());
}

inline std::vector<PackedTensor> relu(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("relu", ins_, a_.str());
}

inline std::vector<PackedTensor> relu6(
    PyRuntime& rt,
    const PackedTensor& data) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  return rt.invoke("relu6", ins_, a_.str());
}

inline std::vector<PackedTensor> repeat(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& repeats,
    const char* axis_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(repeats);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  return rt.invoke("repeat", ins_, a_.str());
}

inline std::vector<PackedTensor> reset_arrays(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    const char* num_arrays_json = nullptr) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  if (num_arrays_json) a_.raw("num_arrays", num_arrays_json);
  return rt.invoke("reset_arrays", ins_, a_.str());
}

inline std::vector<PackedTensor> reshape(
    PyRuntime& rt,
    const PackedTensor& data,
    const char* shape_json = nullptr,
    bool reverse = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (shape_json) a_.raw("shape", shape_json);
  a_.put_bool("reverse", reverse);
  return rt.invoke("reshape", ins_, a_.str());
}

inline std::vector<PackedTensor> reshape_like(
    PyRuntime& rt,
    const PackedTensor& lhs,
    const PackedTensor& rhs,
    const char* lhs_begin_json = nullptr,
    const char* lhs_end_json = nullptr,
    const char* rhs_begin_json = nullptr,
    const char* rhs_end_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(lhs);
  ins_.push_back(rhs);
  detail::JsonBuilder a_;
  if (lhs_begin_json) a_.raw("lhs_begin", lhs_begin_json);
  if (lhs_end_json) a_.raw("lhs_end", lhs_end_json);
  if (rhs_begin_json) a_.raw("rhs_begin", rhs_begin_json);
  if (rhs_end_json) a_.raw("rhs_end", rhs_end_json);
  return rt.invoke("reshape_like", ins_, a_.str());
}

inline std::vector<PackedTensor> reverse(
    PyRuntime& rt,
    const PackedTensor& data,
    long long axis = 0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  a_.put_int("axis", axis);
  return rt.invoke("reverse", ins_, a_.str());
}

inline std::vector<PackedTensor> rint(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("rint", ins_, a_.str());
}

inline std::vector<PackedTensor> rms_norm(
    PyRuntime& rt,
    const PackedTensor& x,
    const PackedTensor& gamma,
    long long axis = -1,
    double eps = 1e-06) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  ins_.push_back(gamma);
  detail::JsonBuilder a_;
  a_.put_int("axis", axis);
  a_.put_num("eps", eps);
  return rt.invoke("rms_norm", ins_, a_.str());
}

inline std::vector<PackedTensor> rms_norm_rotary(
    PyRuntime& rt,
    const PackedTensor& x,
    const PackedTensor& gamma,
    const PackedTensor& positions,
    double theta = 10000.0,
    long long num_heads = 1,
    double eps = 1e-06) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  ins_.push_back(gamma);
  ins_.push_back(positions);
  detail::JsonBuilder a_;
  a_.put_num("theta", theta);
  a_.put_int("num_heads", num_heads);
  a_.put_num("eps", eps);
  return rt.invoke("rms_norm_rotary", ins_, a_.str());
}

inline std::vector<PackedTensor> rmsprop_update(
    PyRuntime& rt,
    const PackedTensor& weight,
    const PackedTensor& grad,
    const PackedTensor& n,
    const PackedTensor& lr,
    double gamma1 = 0.95,
    double epsilon = 1e-08,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    double clip_weights = -1.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(weight);
  ins_.push_back(grad);
  ins_.push_back(n);
  ins_.push_back(lr);
  detail::JsonBuilder a_;
  a_.put_num("gamma1", gamma1);
  a_.put_num("epsilon", epsilon);
  a_.put_num("wd", wd);
  a_.put_num("rescale_grad", rescale_grad);
  a_.put_num("clip_gradient", clip_gradient);
  a_.put_num("clip_weights", clip_weights);
  return rt.invoke("rmsprop_update", ins_, a_.str());
}

inline std::vector<PackedTensor> rmspropalex_update(
    PyRuntime& rt,
    const PackedTensor& weight,
    const PackedTensor& grad,
    const PackedTensor& n,
    const PackedTensor& g_avg,
    const PackedTensor& delta,
    const PackedTensor& lr,
    double gamma1 = 0.95,
    double gamma2 = 0.9,
    double epsilon = 1e-08,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    double clip_weights = -1.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(weight);
  ins_.push_back(grad);
  ins_.push_back(n);
  ins_.push_back(g_avg);
  ins_.push_back(delta);
  ins_.push_back(lr);
  detail::JsonBuilder a_;
  a_.put_num("gamma1", gamma1);
  a_.put_num("gamma2", gamma2);
  a_.put_num("epsilon", epsilon);
  a_.put_num("wd", wd);
  a_.put_num("rescale_grad", rescale_grad);
  a_.put_num("clip_gradient", clip_gradient);
  a_.put_num("clip_weights", clip_weights);
  return rt.invoke("rmspropalex_update", ins_, a_.str());
}

inline std::vector<PackedTensor> rotary_embedding(
    PyRuntime& rt,
    const PackedTensor& x,
    const PackedTensor& positions,
    double theta = 10000.0,
    bool interleaved = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  ins_.push_back(positions);
  detail::JsonBuilder a_;
  a_.put_num("theta", theta);
  a_.put_bool("interleaved", interleaved);
  return rt.invoke("rotary_embedding", ins_, a_.str());
}

inline std::vector<PackedTensor> round(
    PyRuntime& rt,
    const PackedTensor& a,
    long long decimals = 0,
    const char* out_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  detail::JsonBuilder a_;
  a_.put_int("decimals", decimals);
  if (out_json) a_.raw("out", out_json);
  return rt.invoke("round", ins_, a_.str());
}

inline std::vector<PackedTensor> rsqrt(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("rsqrt", ins_, a_.str());
}

inline std::vector<PackedTensor> scatter_nd(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& indices,
    const PackedTensor& shape) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(indices);
  ins_.push_back(shape);
  detail::JsonBuilder a_;
  return rt.invoke("scatter_nd", ins_, a_.str());
}

inline std::vector<PackedTensor> sequence_last(
    PyRuntime& rt,
    const PackedTensor& x,
    const char* sequence_length_json = nullptr,
    bool use_sequence_length = false,
    long long axis = 0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  if (sequence_length_json) a_.raw("sequence_length", sequence_length_json);
  a_.put_bool("use_sequence_length", use_sequence_length);
  a_.put_int("axis", axis);
  return rt.invoke("sequence_last", ins_, a_.str());
}

inline std::vector<PackedTensor> sequence_mask(
    PyRuntime& rt,
    const PackedTensor& x,
    const char* sequence_length_json = nullptr,
    bool use_sequence_length = false,
    double value = 0.0,
    long long axis = 0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  if (sequence_length_json) a_.raw("sequence_length", sequence_length_json);
  a_.put_bool("use_sequence_length", use_sequence_length);
  a_.put_num("value", value);
  a_.put_int("axis", axis);
  return rt.invoke("sequence_mask", ins_, a_.str());
}

inline std::vector<PackedTensor> sequence_reverse(
    PyRuntime& rt,
    const PackedTensor& x,
    const char* sequence_length_json = nullptr,
    bool use_sequence_length = false,
    long long axis = 0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  if (sequence_length_json) a_.raw("sequence_length", sequence_length_json);
  a_.put_bool("use_sequence_length", use_sequence_length);
  a_.put_int("axis", axis);
  return rt.invoke("sequence_reverse", ins_, a_.str());
}

inline std::vector<PackedTensor> sgd_mom_update(
    PyRuntime& rt,
    const PackedTensor& weight,
    const PackedTensor& grad,
    const PackedTensor& mom,
    const PackedTensor& lr,
    double momentum = 0.0,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    bool lazy_update = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(weight);
  ins_.push_back(grad);
  ins_.push_back(mom);
  ins_.push_back(lr);
  detail::JsonBuilder a_;
  a_.put_num("momentum", momentum);
  a_.put_num("wd", wd);
  a_.put_num("rescale_grad", rescale_grad);
  a_.put_num("clip_gradient", clip_gradient);
  a_.put_bool("lazy_update", lazy_update);
  return rt.invoke("sgd_mom_update", ins_, a_.str());
}

inline std::vector<PackedTensor> sgd_update(
    PyRuntime& rt,
    const PackedTensor& weight,
    const PackedTensor& grad,
    const PackedTensor& lr,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    bool lazy_update = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(weight);
  ins_.push_back(grad);
  ins_.push_back(lr);
  detail::JsonBuilder a_;
  a_.put_num("wd", wd);
  a_.put_num("rescale_grad", rescale_grad);
  a_.put_num("clip_gradient", clip_gradient);
  a_.put_bool("lazy_update", lazy_update);
  return rt.invoke("sgd_update", ins_, a_.str());
}

inline std::vector<PackedTensor> shape_array(
    PyRuntime& rt,
    const PackedTensor& data) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  return rt.invoke("shape_array", ins_, a_.str());
}

inline std::vector<PackedTensor> short_conv(
    PyRuntime& rt,
    const PackedTensor& x,
    const PackedTensor& w,
    const std::string& activation = "silu") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  ins_.push_back(w);
  detail::JsonBuilder a_;
  a_.put_str("activation", activation);
  return rt.invoke("short_conv", ins_, a_.str());
}

inline std::vector<PackedTensor> sigmoid(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("sigmoid", ins_, a_.str());
}

inline std::vector<PackedTensor> sign(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("sign", ins_, a_.str());
}

inline std::vector<PackedTensor> signsgd_update(
    PyRuntime& rt,
    const PackedTensor& weight,
    const PackedTensor& grad,
    const PackedTensor& lr,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(weight);
  ins_.push_back(grad);
  ins_.push_back(lr);
  detail::JsonBuilder a_;
  a_.put_num("wd", wd);
  a_.put_num("rescale_grad", rescale_grad);
  a_.put_num("clip_gradient", clip_gradient);
  return rt.invoke("signsgd_update", ins_, a_.str());
}

inline std::vector<PackedTensor> signum_update(
    PyRuntime& rt,
    const PackedTensor& weight,
    const PackedTensor& grad,
    const PackedTensor& mom,
    const PackedTensor& lr,
    double momentum = 0.0,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    double wd_lh = 0.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(weight);
  ins_.push_back(grad);
  ins_.push_back(mom);
  ins_.push_back(lr);
  detail::JsonBuilder a_;
  a_.put_num("momentum", momentum);
  a_.put_num("wd", wd);
  a_.put_num("rescale_grad", rescale_grad);
  a_.put_num("clip_gradient", clip_gradient);
  a_.put_num("wd_lh", wd_lh);
  return rt.invoke("signum_update", ins_, a_.str());
}

inline std::vector<PackedTensor> silu(
    PyRuntime& rt,
    const PackedTensor& data) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  return rt.invoke("silu", ins_, a_.str());
}

inline std::vector<PackedTensor> sin(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("sin", ins_, a_.str());
}

inline std::vector<PackedTensor> sinh(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("sinh", ins_, a_.str());
}

inline std::vector<PackedTensor> size_array(
    PyRuntime& rt,
    const PackedTensor& data) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  return rt.invoke("size_array", ins_, a_.str());
}

inline std::vector<PackedTensor> slice(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& begin,
    const PackedTensor& end,
    const char* step_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(begin);
  ins_.push_back(end);
  detail::JsonBuilder a_;
  if (step_json) a_.raw("step", step_json);
  return rt.invoke("slice", ins_, a_.str());
}

inline std::vector<PackedTensor> slice_axis(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& axis,
    const PackedTensor& begin,
    const PackedTensor& end) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(axis);
  ins_.push_back(begin);
  ins_.push_back(end);
  detail::JsonBuilder a_;
  return rt.invoke("slice_axis", ins_, a_.str());
}

inline std::vector<PackedTensor> slice_like(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& shape_like,
    const char* axes_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(shape_like);
  detail::JsonBuilder a_;
  if (axes_json) a_.raw("axes", axes_json);
  return rt.invoke("slice_like", ins_, a_.str());
}

inline std::vector<PackedTensor> smooth_l1(
    PyRuntime& rt,
    const PackedTensor& data,
    double scalar = 1.0) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  a_.put_num("scalar", scalar);
  return rt.invoke("smooth_l1", ins_, a_.str());
}

inline std::vector<PackedTensor> softmax(
    PyRuntime& rt,
    const PackedTensor& x,
    long long axis = -1,
    const char* length_json = nullptr,
    const char* temperature_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  a_.put_int("axis", axis);
  if (length_json) a_.raw("length", length_json);
  if (temperature_json) a_.raw("temperature", temperature_json);
  return rt.invoke("softmax", ins_, a_.str());
}

inline std::vector<PackedTensor> softmax_cross_entropy(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& label) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(label);
  detail::JsonBuilder a_;
  return rt.invoke("softmax_cross_entropy", ins_, a_.str());
}

inline std::vector<PackedTensor> softmax_output(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& label,
    double grad_scale = 1.0,
    long long ignore_label = -1,
    bool use_ignore = false,
    bool multi_output = false,
    const std::string& normalization = "null",
    const std::string& extra_attrs = "") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(label);
  detail::JsonBuilder a_;
  a_.put_num("grad_scale", grad_scale);
  a_.put_int("ignore_label", ignore_label);
  a_.put_bool("use_ignore", use_ignore);
  a_.put_bool("multi_output", multi_output);
  a_.put_str("normalization", normalization);
  return rt.invoke("softmax_output", ins_, detail::merge(a_.str(), extra_attrs));
}

inline std::vector<PackedTensor> softmin(
    PyRuntime& rt,
    const PackedTensor& x,
    long long axis = -1) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  a_.put_int("axis", axis);
  return rt.invoke("softmin", ins_, a_.str());
}

inline std::vector<PackedTensor> softsign(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("softsign", ins_, a_.str());
}

inline std::vector<PackedTensor> sort(
    PyRuntime& rt,
    const PackedTensor& data,
    long long axis = -1,
    bool is_ascend = true) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  a_.put_int("axis", axis);
  a_.put_bool("is_ascend", is_ascend);
  return rt.invoke("sort", ins_, a_.str());
}

inline std::vector<PackedTensor> space_to_depth(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& block_size) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(block_size);
  detail::JsonBuilder a_;
  return rt.invoke("space_to_depth", ins_, a_.str());
}

inline std::vector<PackedTensor> split(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& num_outputs,
    long long axis = 1,
    bool squeeze_axis = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(num_outputs);
  detail::JsonBuilder a_;
  a_.put_int("axis", axis);
  a_.put_bool("squeeze_axis", squeeze_axis);
  return rt.invoke("split", ins_, a_.str());
}

inline std::vector<PackedTensor> sqrt(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("sqrt", ins_, a_.str());
}

inline std::vector<PackedTensor> square(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("square", ins_, a_.str());
}

inline std::vector<PackedTensor> squeeze(
    PyRuntime& rt,
    const PackedTensor& data,
    const char* axis_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  return rt.invoke("squeeze", ins_, a_.str());
}

inline std::vector<PackedTensor> stack(
    PyRuntime& rt,
    const std::vector<PackedTensor>& inputs,
    long long axis = 0) {
  std::vector<PackedTensor> ins_(inputs);
  detail::JsonBuilder a_;
  a_.put_int("axis", axis);
  return rt.invoke("stack", ins_, a_.str());
}

inline std::vector<PackedTensor> stop_gradient(
    PyRuntime& rt,
    const PackedTensor& data) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  return rt.invoke("stop_gradient", ins_, a_.str());
}

inline std::vector<PackedTensor> sum(
    PyRuntime& rt,
    const PackedTensor& data,
    const char* axis_json = nullptr,
    bool keepdims = false,
    bool exclude = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  a_.put_bool("keepdims", keepdims);
  a_.put_bool("exclude", exclude);
  return rt.invoke("sum", ins_, a_.str());
}

inline std::vector<PackedTensor> sum_axis(
    PyRuntime& rt,
    const PackedTensor& data,
    const char* axis_json = nullptr,
    bool keepdims = false,
    bool exclude = false) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (axis_json) a_.raw("axis", axis_json);
  a_.put_bool("keepdims", keepdims);
  a_.put_bool("exclude", exclude);
  return rt.invoke("sum_axis", ins_, a_.str());
}

inline std::vector<PackedTensor> swapaxes(
    PyRuntime& rt,
    const PackedTensor& data,
    long long dim1 = 0,
    long long dim2 = 1) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  a_.put_int("dim1", dim1);
  a_.put_int("dim2", dim2);
  return rt.invoke("swapaxes", ins_, a_.str());
}

inline std::vector<PackedTensor> take(
    PyRuntime& rt,
    const PackedTensor& a,
    const PackedTensor& indices,
    long long axis = 0,
    const std::string& mode = "clip") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(a);
  ins_.push_back(indices);
  detail::JsonBuilder a_;
  a_.put_int("axis", axis);
  a_.put_str("mode", mode);
  return rt.invoke("take", ins_, a_.str());
}

inline std::vector<PackedTensor> tan(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("tan", ins_, a_.str());
}

inline std::vector<PackedTensor> tanh(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("tanh", ins_, a_.str());
}

inline std::vector<PackedTensor> tile(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& reps) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(reps);
  detail::JsonBuilder a_;
  return rt.invoke("tile", ins_, a_.str());
}

inline std::vector<PackedTensor> topk(
    PyRuntime& rt,
    const PackedTensor& x,
    long long k = 1,
    long long axis = -1,
    const std::string& ret_typ = "indices",
    bool is_ascend = false,
    const std::string& dtype = "float32") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  a_.put_int("k", k);
  a_.put_int("axis", axis);
  a_.put_str("ret_typ", ret_typ);
  a_.put_bool("is_ascend", is_ascend);
  a_.put_str("dtype", dtype);
  return rt.invoke("topk", ins_, a_.str());
}

inline std::vector<PackedTensor> trace(
    PyRuntime& rt,
    const PackedTensor& data,
    long long offset = 0,
    long long axis1 = 0,
    long long axis2 = 1) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  a_.put_int("offset", offset);
  a_.put_int("axis1", axis1);
  a_.put_int("axis2", axis2);
  return rt.invoke("trace", ins_, a_.str());
}

inline std::vector<PackedTensor> transpose(
    PyRuntime& rt,
    const PackedTensor& data,
    const char* axes_json = nullptr) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  detail::JsonBuilder a_;
  if (axes_json) a_.raw("axes", axes_json);
  return rt.invoke("transpose", ins_, a_.str());
}

inline std::vector<PackedTensor> trunc(
    PyRuntime& rt,
    const PackedTensor& x) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  return rt.invoke("trunc", ins_, a_.str());
}

inline std::vector<PackedTensor> unravel_index(
    PyRuntime& rt,
    const PackedTensor& data,
    const PackedTensor& shape) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(data);
  ins_.push_back(shape);
  detail::JsonBuilder a_;
  return rt.invoke("unravel_index", ins_, a_.str());
}

inline std::vector<PackedTensor> upsampling(
    PyRuntime& rt,
    const PackedTensor& x,
    long long scale = 2,
    const std::string& sample_type = "nearest") {
  std::vector<PackedTensor> ins_;
  ins_.push_back(x);
  detail::JsonBuilder a_;
  a_.put_int("scale", scale);
  a_.put_str("sample_type", sample_type);
  return rt.invoke("upsampling", ins_, a_.str());
}

inline std::vector<PackedTensor> where(
    PyRuntime& rt,
    const PackedTensor& condition,
    const PackedTensor& x,
    const PackedTensor& y) {
  std::vector<PackedTensor> ins_;
  ins_.push_back(condition);
  ins_.push_back(x);
  ins_.push_back(y);
  detail::JsonBuilder a_;
  return rt.invoke("where", ins_, a_.str());
}


}  // namespace op
}  // namespace mxtpu
