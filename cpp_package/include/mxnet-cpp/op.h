// GENERATED by cpp_package/scripts/gen_op_wrappers.py — do not edit.
// One Symbol-building function per registered operator (reference
// parity: cpp-package/include/mxnet-cpp/op.h from OpWrapperGenerator.py).
#ifndef MXNET_CPP_OP_H_
#define MXNET_CPP_OP_H_

#include <string>
#include <vector>

#include "symbol.hpp"

namespace mxnet {
namespace cpp {
namespace op {

inline Symbol Activation(const std::string &symbol_name,
    Symbol data,
    const std::string &act_type) {
  SymbolBuilder builder_("Activation");
  builder_.SetParam("act_type", act_type);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol AdaptiveAvgPooling2D(const std::string &symbol_name,
    Symbol data,
    const std::string &output_size = "()") {
  SymbolBuilder builder_("AdaptiveAvgPooling2D");
  builder_.SetParam("output_size", output_size);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol AttentionConvolution(const std::string &symbol_name,
    Symbol data,
    Symbol attention,
    Symbol weight,
    Symbol bias,
    const std::string &kernel,
    int num_filter,
    const std::string &stride = "()",
    const std::string &dilate = "()",
    const std::string &pad = "()",
    int num_group = 1,
    bool no_bias = false,
    int workspace = 1024,
    bool cudnn_off = false) {
  SymbolBuilder builder_("AttentionConvolution");
  builder_.SetParam("kernel", kernel);
  builder_.SetParam("num_filter", num_filter);
  builder_.SetParam("stride", stride);
  builder_.SetParam("dilate", dilate);
  builder_.SetParam("pad", pad);
  builder_.SetParam("num_group", num_group);
  builder_.SetParam("no_bias", no_bias ? 1 : 0);
  builder_.SetParam("workspace", workspace);
  builder_.SetParam("cudnn_off", cudnn_off ? 1 : 0);
  builder_.SetInput("data", data);
  builder_.SetInput("attention", attention);
  builder_.SetInput("weight", weight);
  builder_.SetInput("bias", bias);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol BatchNorm(const std::string &symbol_name,
    Symbol data,
    Symbol gamma,
    Symbol beta,
    Symbol moving_mean,
    Symbol moving_var,
    double eps = 0.001,
    double momentum = 0.9,
    bool fix_gamma = true,
    bool use_global_stats = false,
    bool output_mean_var = false,
    int axis = 1,
    bool cudnn_off = false) {
  SymbolBuilder builder_("BatchNorm");
  builder_.SetParam("eps", eps);
  builder_.SetParam("momentum", momentum);
  builder_.SetParam("fix_gamma", fix_gamma ? 1 : 0);
  builder_.SetParam("use_global_stats", use_global_stats ? 1 : 0);
  builder_.SetParam("output_mean_var", output_mean_var ? 1 : 0);
  builder_.SetParam("axis", axis);
  builder_.SetParam("cudnn_off", cudnn_off ? 1 : 0);
  builder_.SetInput("data", data);
  builder_.SetInput("gamma", gamma);
  builder_.SetInput("beta", beta);
  builder_.SetInput("moving_mean", moving_mean);
  builder_.SetInput("moving_var", moving_var);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol BatchNorm_v1(const std::string &symbol_name,
    Symbol data,
    Symbol gamma,
    Symbol beta,
    Symbol moving_mean,
    Symbol moving_var,
    double eps = 0.001,
    double momentum = 0.9,
    bool fix_gamma = true,
    bool use_global_stats = false,
    bool output_mean_var = false,
    int axis = 1,
    bool cudnn_off = false) {
  SymbolBuilder builder_("BatchNorm_v1");
  builder_.SetParam("eps", eps);
  builder_.SetParam("momentum", momentum);
  builder_.SetParam("fix_gamma", fix_gamma ? 1 : 0);
  builder_.SetParam("use_global_stats", use_global_stats ? 1 : 0);
  builder_.SetParam("output_mean_var", output_mean_var ? 1 : 0);
  builder_.SetParam("axis", axis);
  builder_.SetParam("cudnn_off", cudnn_off ? 1 : 0);
  builder_.SetInput("data", data);
  builder_.SetInput("gamma", gamma);
  builder_.SetInput("beta", beta);
  builder_.SetInput("moving_mean", moving_mean);
  builder_.SetInput("moving_var", moving_var);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol BilinearResize2D(const std::string &symbol_name,
    Symbol data,
    int height,
    int width) {
  SymbolBuilder builder_("BilinearResize2D");
  builder_.SetParam("height", height);
  builder_.SetParam("width", width);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol BilinearSampler(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("BilinearSampler");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol BlockGrad(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("BlockGrad");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol CTCLoss(const std::string &symbol_name,
    const std::vector<Symbol> &data,
    bool use_data_lengths = false,
    bool use_label_lengths = false,
    const std::string &blank_label = "first") {
  SymbolBuilder builder_("CTCLoss");
  builder_.SetParam("use_data_lengths", use_data_lengths ? 1 : 0);
  builder_.SetParam("use_label_lengths", use_label_lengths ? 1 : 0);
  builder_.SetParam("blank_label", blank_label);
  for (const auto &s : data) builder_.AddInput(s);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol Cast(const std::string &symbol_name,
    Symbol data,
    const std::string &dtype) {
  SymbolBuilder builder_("Cast");
  builder_.SetParam("dtype", dtype);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol ChannelOperator(const std::string &symbol_name,
    const std::vector<Symbol> &data,
    const std::string &op_type,
    int group,
    const std::string &pick_type = "Label_Pick") {
  SymbolBuilder builder_("ChannelOperator");
  builder_.SetParam("op_type", op_type);
  builder_.SetParam("group", group);
  builder_.SetParam("pick_type", pick_type);
  for (const auto &s : data) builder_.AddInput(s);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol Concat(const std::string &symbol_name,
    const std::vector<Symbol> &data,
    int dim = 1) {
  SymbolBuilder builder_("Concat");
  builder_.SetParam("dim", dim);
  builder_.SetParam("num_args", data.size());
  for (const auto &s : data) builder_.AddInput(s);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol Convolution(const std::string &symbol_name,
    Symbol data,
    Symbol weight,
    Symbol bias,
    const std::string &kernel,
    int num_filter,
    const std::string &stride = "()",
    const std::string &dilate = "()",
    const std::string &pad = "()",
    int num_group = 1,
    bool no_bias = false,
    int workspace = 1024,
    bool cudnn_off = false) {
  SymbolBuilder builder_("Convolution");
  builder_.SetParam("kernel", kernel);
  builder_.SetParam("num_filter", num_filter);
  builder_.SetParam("stride", stride);
  builder_.SetParam("dilate", dilate);
  builder_.SetParam("pad", pad);
  builder_.SetParam("num_group", num_group);
  builder_.SetParam("no_bias", no_bias ? 1 : 0);
  builder_.SetParam("workspace", workspace);
  builder_.SetParam("cudnn_off", cudnn_off ? 1 : 0);
  builder_.SetInput("data", data);
  builder_.SetInput("weight", weight);
  builder_.SetInput("bias", bias);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol Convolution_v1(const std::string &symbol_name,
    Symbol data,
    Symbol weight,
    Symbol bias,
    const std::string &kernel,
    int num_filter,
    const std::string &stride = "()",
    const std::string &dilate = "()",
    const std::string &pad = "()",
    int num_group = 1,
    bool no_bias = false,
    int workspace = 1024,
    bool cudnn_off = false) {
  SymbolBuilder builder_("Convolution_v1");
  builder_.SetParam("kernel", kernel);
  builder_.SetParam("num_filter", num_filter);
  builder_.SetParam("stride", stride);
  builder_.SetParam("dilate", dilate);
  builder_.SetParam("pad", pad);
  builder_.SetParam("num_group", num_group);
  builder_.SetParam("no_bias", no_bias ? 1 : 0);
  builder_.SetParam("workspace", workspace);
  builder_.SetParam("cudnn_off", cudnn_off ? 1 : 0);
  builder_.SetInput("data", data);
  builder_.SetInput("weight", weight);
  builder_.SetInput("bias", bias);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol Correlation(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    int kernel_size = 1,
    int max_displacement = 1,
    int stride1 = 1,
    int stride2 = 1,
    int pad_size = 0,
    bool is_multiply = true) {
  SymbolBuilder builder_("Correlation");
  builder_.SetParam("kernel_size", kernel_size);
  builder_.SetParam("max_displacement", max_displacement);
  builder_.SetParam("stride1", stride1);
  builder_.SetParam("stride2", stride2);
  builder_.SetParam("pad_size", pad_size);
  builder_.SetParam("is_multiply", is_multiply ? 1 : 0);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol Crop(const std::string &symbol_name,
    const std::vector<Symbol> &data,
    const std::string &offset = "(0, 0)",
    const std::string &h_w = "(0, 0)",
    bool center_crop = false) {
  SymbolBuilder builder_("Crop");
  builder_.SetParam("offset", offset);
  builder_.SetParam("h_w", h_w);
  builder_.SetParam("center_crop", center_crop ? 1 : 0);
  builder_.SetParam("num_args", data.size());
  for (const auto &s : data) builder_.AddInput(s);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol Custom(const std::string &symbol_name,
    const std::vector<Symbol> &data,
    const std::string &op_type) {
  SymbolBuilder builder_("Custom");
  builder_.SetParam("op_type", op_type);
  for (const auto &s : data) builder_.AddInput(s);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol Deconvolution(const std::string &symbol_name,
    Symbol data,
    Symbol weight,
    Symbol bias,
    const std::string &kernel,
    int num_filter,
    const std::string &stride = "()",
    const std::string &dilate = "()",
    const std::string &pad = "()",
    int num_group = 1,
    bool no_bias = false,
    int workspace = 1024,
    bool cudnn_off = false,
    const std::string &adj = "()",
    const std::string &target_shape = "()") {
  SymbolBuilder builder_("Deconvolution");
  builder_.SetParam("kernel", kernel);
  builder_.SetParam("num_filter", num_filter);
  builder_.SetParam("stride", stride);
  builder_.SetParam("dilate", dilate);
  builder_.SetParam("pad", pad);
  builder_.SetParam("num_group", num_group);
  builder_.SetParam("no_bias", no_bias ? 1 : 0);
  builder_.SetParam("workspace", workspace);
  builder_.SetParam("cudnn_off", cudnn_off ? 1 : 0);
  builder_.SetParam("adj", adj);
  builder_.SetParam("target_shape", target_shape);
  builder_.SetInput("data", data);
  builder_.SetInput("weight", weight);
  builder_.SetInput("bias", bias);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol DeformableConvolution(const std::string &symbol_name,
    const std::vector<Symbol> &data,
    const std::string &kernel,
    int num_filter,
    const std::string &stride = "()",
    const std::string &dilate = "()",
    const std::string &pad = "()",
    int num_group = 1,
    int num_deformable_group = 1,
    int workspace = 1024,
    bool no_bias = false) {
  SymbolBuilder builder_("DeformableConvolution");
  builder_.SetParam("kernel", kernel);
  builder_.SetParam("num_filter", num_filter);
  builder_.SetParam("stride", stride);
  builder_.SetParam("dilate", dilate);
  builder_.SetParam("pad", pad);
  builder_.SetParam("num_group", num_group);
  builder_.SetParam("num_deformable_group", num_deformable_group);
  builder_.SetParam("workspace", workspace);
  builder_.SetParam("no_bias", no_bias ? 1 : 0);
  for (const auto &s : data) builder_.AddInput(s);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol DeformablePSROIPooling(const std::string &symbol_name,
    const std::vector<Symbol> &data,
    double spatial_scale,
    int output_dim,
    int group_size,
    int pooled_size,
    int part_size = 0,
    int sample_per_part = 1,
    double trans_std = 0.0,
    bool no_trans = false) {
  SymbolBuilder builder_("DeformablePSROIPooling");
  builder_.SetParam("spatial_scale", spatial_scale);
  builder_.SetParam("output_dim", output_dim);
  builder_.SetParam("group_size", group_size);
  builder_.SetParam("pooled_size", pooled_size);
  builder_.SetParam("part_size", part_size);
  builder_.SetParam("sample_per_part", sample_per_part);
  builder_.SetParam("trans_std", trans_std);
  builder_.SetParam("no_trans", no_trans ? 1 : 0);
  for (const auto &s : data) builder_.AddInput(s);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol DeltaAttention(const std::string &symbol_name,
    Symbol data,
    Symbol query_weight,
    Symbol key_weight,
    Symbol value_weight,
    Symbol q_conv_weight,
    Symbol k_conv_weight,
    Symbol v_conv_weight,
    Symbol f_a_weight,
    Symbol f_b_weight,
    Symbol A_log,
    Symbol dt_bias,
    Symbol beta_weight,
    Symbol g_a_weight,
    Symbol g_b_weight,
    Symbol o_norm_gamma,
    Symbol out_proj_weight,
    int num_heads,
    int head_dim,
    int conv_kernel = 4,
    double eps = 1e-05) {
  SymbolBuilder builder_("DeltaAttention");
  builder_.SetParam("num_heads", num_heads);
  builder_.SetParam("head_dim", head_dim);
  builder_.SetParam("conv_kernel", conv_kernel);
  builder_.SetParam("eps", eps);
  builder_.SetInput("data", data);
  builder_.SetInput("query_weight", query_weight);
  builder_.SetInput("key_weight", key_weight);
  builder_.SetInput("value_weight", value_weight);
  builder_.SetInput("q_conv_weight", q_conv_weight);
  builder_.SetInput("k_conv_weight", k_conv_weight);
  builder_.SetInput("v_conv_weight", v_conv_weight);
  builder_.SetInput("f_a_weight", f_a_weight);
  builder_.SetInput("f_b_weight", f_b_weight);
  builder_.SetInput("A_log", A_log);
  builder_.SetInput("dt_bias", dt_bias);
  builder_.SetInput("beta_weight", beta_weight);
  builder_.SetInput("g_a_weight", g_a_weight);
  builder_.SetInput("g_b_weight", g_b_weight);
  builder_.SetInput("o_norm_gamma", o_norm_gamma);
  builder_.SetInput("out_proj_weight", out_proj_weight);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol Dropout(const std::string &symbol_name,
    Symbol data,
    double p = 0.5,
    const std::string &mode = "training",
    const std::string &axes = "()",
    bool cudnn_off = false) {
  SymbolBuilder builder_("Dropout");
  builder_.SetParam("p", p);
  builder_.SetParam("mode", mode);
  builder_.SetParam("axes", axes);
  builder_.SetParam("cudnn_off", cudnn_off ? 1 : 0);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol DynamicConvolution(const std::string &symbol_name,
    Symbol data,
    Symbol across_weight,
    Symbol within_weight,
    const std::string &kernel,
    int num_filter,
    const std::string &stride = "()",
    const std::string &dilate = "()",
    const std::string &pad = "()",
    int num_group = 1,
    bool no_bias = false,
    int workspace = 1024,
    bool cudnn_off = false,
    const std::string &sample = "()",
    const std::string &s_stride = "()") {
  SymbolBuilder builder_("DynamicConvolution");
  builder_.SetParam("kernel", kernel);
  builder_.SetParam("num_filter", num_filter);
  builder_.SetParam("stride", stride);
  builder_.SetParam("dilate", dilate);
  builder_.SetParam("pad", pad);
  builder_.SetParam("num_group", num_group);
  builder_.SetParam("no_bias", no_bias ? 1 : 0);
  builder_.SetParam("workspace", workspace);
  builder_.SetParam("cudnn_off", cudnn_off ? 1 : 0);
  builder_.SetParam("sample", sample);
  builder_.SetParam("s_stride", s_stride);
  builder_.SetInput("data", data);
  builder_.SetInput("across_weight", across_weight);
  builder_.SetInput("within_weight", within_weight);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol ElementWiseSum(const std::string &symbol_name,
    const std::vector<Symbol> &data) {
  SymbolBuilder builder_("ElementWiseSum");
  for (const auto &s : data) builder_.AddInput(s);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol Embedding(const std::string &symbol_name,
    Symbol data,
    Symbol weight,
    int input_dim,
    int output_dim,
    const std::string &dtype = "float32",
    bool sparse_grad = false) {
  SymbolBuilder builder_("Embedding");
  builder_.SetParam("input_dim", input_dim);
  builder_.SetParam("output_dim", output_dim);
  builder_.SetParam("dtype", dtype);
  builder_.SetParam("sparse_grad", sparse_grad ? 1 : 0);
  builder_.SetInput("data", data);
  builder_.SetInput("weight", weight);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol Flatten(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("Flatten");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol FullyConnected(const std::string &symbol_name,
    Symbol data,
    Symbol weight,
    Symbol bias,
    int num_hidden,
    bool no_bias = false,
    bool flatten = true) {
  SymbolBuilder builder_("FullyConnected");
  builder_.SetParam("num_hidden", num_hidden);
  builder_.SetParam("no_bias", no_bias ? 1 : 0);
  builder_.SetParam("flatten", flatten ? 1 : 0);
  builder_.SetInput("data", data);
  builder_.SetInput("weight", weight);
  builder_.SetInput("bias", bias);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol FullyConnected_v1(const std::string &symbol_name,
    Symbol data,
    Symbol weight,
    Symbol bias,
    int num_hidden,
    bool no_bias = false,
    bool flatten = true) {
  SymbolBuilder builder_("FullyConnected_v1");
  builder_.SetParam("num_hidden", num_hidden);
  builder_.SetParam("no_bias", no_bias ? 1 : 0);
  builder_.SetParam("flatten", flatten ? 1 : 0);
  builder_.SetInput("data", data);
  builder_.SetInput("weight", weight);
  builder_.SetInput("bias", bias);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol GridGenerator(const std::string &symbol_name,
    Symbol data,
    const std::string &transform_type,
    const std::string &target_shape = "(0, 0)") {
  SymbolBuilder builder_("GridGenerator");
  builder_.SetParam("transform_type", transform_type);
  builder_.SetParam("target_shape", target_shape);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol IdentityAttachKLSparseReg(const std::string &symbol_name,
    const std::vector<Symbol> &data,
    double sparseness_target = 0.1,
    double penalty = 0.001,
    double momentum = 0.9) {
  SymbolBuilder builder_("IdentityAttachKLSparseReg");
  builder_.SetParam("sparseness_target", sparseness_target);
  builder_.SetParam("penalty", penalty);
  builder_.SetParam("momentum", momentum);
  for (const auto &s : data) builder_.AddInput(s);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol InstanceNorm(const std::string &symbol_name,
    Symbol data,
    Symbol gamma,
    Symbol beta,
    double eps = 0.001) {
  SymbolBuilder builder_("InstanceNorm");
  builder_.SetParam("eps", eps);
  builder_.SetInput("data", data);
  builder_.SetInput("gamma", gamma);
  builder_.SetInput("beta", beta);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol L2Normalization(const std::string &symbol_name,
    Symbol data,
    double eps = 1e-10,
    const std::string &mode = "instance") {
  SymbolBuilder builder_("L2Normalization");
  builder_.SetParam("eps", eps);
  builder_.SetParam("mode", mode);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol LRN(const std::string &symbol_name,
    Symbol data,
    int nsize,
    double alpha = 0.0001,
    double beta = 0.75,
    double knorm = 2.0) {
  SymbolBuilder builder_("LRN");
  builder_.SetParam("nsize", nsize);
  builder_.SetParam("alpha", alpha);
  builder_.SetParam("beta", beta);
  builder_.SetParam("knorm", knorm);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol LayerNorm(const std::string &symbol_name,
    Symbol data,
    Symbol gamma,
    Symbol beta,
    int axis = -1,
    double eps = 1e-05,
    bool output_mean_var = false) {
  SymbolBuilder builder_("LayerNorm");
  builder_.SetParam("axis", axis);
  builder_.SetParam("eps", eps);
  builder_.SetParam("output_mean_var", output_mean_var ? 1 : 0);
  builder_.SetInput("data", data);
  builder_.SetInput("gamma", gamma);
  builder_.SetInput("beta", beta);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol LeakyReLU(const std::string &symbol_name,
    Symbol data,
    Symbol gamma,
    const std::string &act_type = "leaky",
    double slope = 0.25,
    double lower_bound = 0.125,
    double upper_bound = 0.334) {
  SymbolBuilder builder_("LeakyReLU");
  builder_.SetParam("act_type", act_type);
  builder_.SetParam("slope", slope);
  builder_.SetParam("lower_bound", lower_bound);
  builder_.SetParam("upper_bound", upper_bound);
  builder_.SetInput("data", data);
  builder_.SetInput("gamma", gamma);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol LinearRegressionOutput(const std::string &symbol_name,
    Symbol data,
    Symbol label,
    double grad_scale = 1.0) {
  SymbolBuilder builder_("LinearRegressionOutput");
  builder_.SetParam("grad_scale", grad_scale);
  builder_.SetInput("data", data);
  builder_.SetInput("label", label);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol LogisticRegressionOutput(const std::string &symbol_name,
    Symbol data,
    Symbol label,
    double grad_scale = 1.0) {
  SymbolBuilder builder_("LogisticRegressionOutput");
  builder_.SetParam("grad_scale", grad_scale);
  builder_.SetInput("data", data);
  builder_.SetInput("label", label);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol MAERegressionOutput(const std::string &symbol_name,
    Symbol data,
    Symbol label,
    double grad_scale = 1.0) {
  SymbolBuilder builder_("MAERegressionOutput");
  builder_.SetParam("grad_scale", grad_scale);
  builder_.SetInput("data", data);
  builder_.SetInput("label", label);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol MakeLoss(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("MakeLoss");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol MultiBoxDetection(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    Symbol data2,
    bool clip = true,
    double threshold = 0.01,
    int background_id = 0,
    double nms_threshold = 0.5,
    bool force_suppress = false,
    const std::string &variances = "(0.1, 0.1, 0.2, 0.2)",
    int nms_topk = -1) {
  SymbolBuilder builder_("MultiBoxDetection");
  builder_.SetParam("clip", clip ? 1 : 0);
  builder_.SetParam("threshold", threshold);
  builder_.SetParam("background_id", background_id);
  builder_.SetParam("nms_threshold", nms_threshold);
  builder_.SetParam("force_suppress", force_suppress ? 1 : 0);
  builder_.SetParam("variances", variances);
  builder_.SetParam("nms_topk", nms_topk);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  builder_.SetInput("data2", data2);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol MultiBoxPrior(const std::string &symbol_name,
    Symbol data,
    const std::string &sizes = "(1)",
    const std::string &ratios = "(1)",
    bool clip = false,
    const std::string &steps = "(-1, -1)",
    const std::string &offsets = "(0.5, 0.5)") {
  SymbolBuilder builder_("MultiBoxPrior");
  builder_.SetParam("sizes", sizes);
  builder_.SetParam("ratios", ratios);
  builder_.SetParam("clip", clip ? 1 : 0);
  builder_.SetParam("steps", steps);
  builder_.SetParam("offsets", offsets);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol MultiBoxTarget(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    Symbol data2,
    double overlap_threshold = 0.5,
    double ignore_label = -1.0,
    double negative_mining_ratio = -1.0,
    double negative_mining_thresh = 0.5,
    int minimum_negative_samples = 0,
    const std::string &variances = "(0.1, 0.1, 0.2, 0.2)") {
  SymbolBuilder builder_("MultiBoxTarget");
  builder_.SetParam("overlap_threshold", overlap_threshold);
  builder_.SetParam("ignore_label", ignore_label);
  builder_.SetParam("negative_mining_ratio", negative_mining_ratio);
  builder_.SetParam("negative_mining_thresh", negative_mining_thresh);
  builder_.SetParam("minimum_negative_samples", minimum_negative_samples);
  builder_.SetParam("variances", variances);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  builder_.SetInput("data2", data2);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol MultiHeadAttention(const std::string &symbol_name,
    Symbol data,
    Symbol query_weight,
    Symbol key_weight,
    Symbol value_weight,
    Symbol out_proj_weight,
    Symbol q_norm_gamma,
    Symbol k_norm_gamma,
    Symbol q_a_weight,
    Symbol q_a_norm_gamma,
    Symbol q_b_weight,
    Symbol kv_a_weight,
    Symbol kv_a_norm_gamma,
    Symbol kv_b_weight,
    int num_heads,
    bool causal = true,
    int num_kv_heads = 0,
    bool qk_norm = false,
    double rope_theta = 0.0,
    double eps = 1e-05,
    int head_dim = 0,
    int window = 0,
    const std::string &rope_yarn = "()",
    int q_lora_rank = 0,
    int kv_lora_rank = 0,
    int qk_rope_head_dim = 0,
    int v_head_dim = 0,
    int diffusion_block = 0) {
  SymbolBuilder builder_("MultiHeadAttention");
  builder_.SetParam("num_heads", num_heads);
  builder_.SetParam("causal", causal ? 1 : 0);
  builder_.SetParam("num_kv_heads", num_kv_heads);
  builder_.SetParam("qk_norm", qk_norm ? 1 : 0);
  builder_.SetParam("rope_theta", rope_theta);
  builder_.SetParam("eps", eps);
  builder_.SetParam("head_dim", head_dim);
  builder_.SetParam("window", window);
  builder_.SetParam("rope_yarn", rope_yarn);
  builder_.SetParam("q_lora_rank", q_lora_rank);
  builder_.SetParam("kv_lora_rank", kv_lora_rank);
  builder_.SetParam("qk_rope_head_dim", qk_rope_head_dim);
  builder_.SetParam("v_head_dim", v_head_dim);
  builder_.SetParam("diffusion_block", diffusion_block);
  builder_.SetInput("data", data);
  builder_.SetInput("query_weight", query_weight);
  builder_.SetInput("key_weight", key_weight);
  builder_.SetInput("value_weight", value_weight);
  builder_.SetInput("out_proj_weight", out_proj_weight);
  builder_.SetInput("q_norm_gamma", q_norm_gamma);
  builder_.SetInput("k_norm_gamma", k_norm_gamma);
  builder_.SetInput("q_a_weight", q_a_weight);
  builder_.SetInput("q_a_norm_gamma", q_a_norm_gamma);
  builder_.SetInput("q_b_weight", q_b_weight);
  builder_.SetInput("kv_a_weight", kv_a_weight);
  builder_.SetInput("kv_a_norm_gamma", kv_a_norm_gamma);
  builder_.SetInput("kv_b_weight", kv_b_weight);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol MultiProposal(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    Symbol data2,
    int rpn_pre_nms_top_n = 6000,
    int rpn_post_nms_top_n = 300,
    double threshold = 0.7,
    int rpn_min_size = 16,
    const std::string &scales = "(4, 8, 16, 32)",
    const std::string &ratios = "(0.5, 1, 2)",
    int feature_stride = 16,
    bool output_score = false,
    bool iou_loss = false) {
  SymbolBuilder builder_("MultiProposal");
  builder_.SetParam("rpn_pre_nms_top_n", rpn_pre_nms_top_n);
  builder_.SetParam("rpn_post_nms_top_n", rpn_post_nms_top_n);
  builder_.SetParam("threshold", threshold);
  builder_.SetParam("rpn_min_size", rpn_min_size);
  builder_.SetParam("scales", scales);
  builder_.SetParam("ratios", ratios);
  builder_.SetParam("feature_stride", feature_stride);
  builder_.SetParam("output_score", output_score ? 1 : 0);
  builder_.SetParam("iou_loss", iou_loss ? 1 : 0);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  builder_.SetInput("data2", data2);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol NewReorg(const std::string &symbol_name,
    Symbol data,
    int stride = 2) {
  SymbolBuilder builder_("NewReorg");
  builder_.SetParam("stride", stride);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol PSROIPooling(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    double spatial_scale,
    int output_dim,
    int pooled_size,
    int group_size = 0) {
  SymbolBuilder builder_("PSROIPooling");
  builder_.SetParam("spatial_scale", spatial_scale);
  builder_.SetParam("output_dim", output_dim);
  builder_.SetParam("pooled_size", pooled_size);
  builder_.SetParam("group_size", group_size);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol Pad(const std::string &symbol_name,
    Symbol data,
    const std::string &pad_width,
    const std::string &mode = "constant",
    double constant_value = 0.0) {
  SymbolBuilder builder_("Pad");
  builder_.SetParam("pad_width", pad_width);
  builder_.SetParam("mode", mode);
  builder_.SetParam("constant_value", constant_value);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol Pooling(const std::string &symbol_name,
    Symbol data,
    const std::string &kernel = "()",
    const std::string &pool_type = "max",
    bool global_pool = false,
    bool cudnn_off = false,
    const std::string &pooling_convention = "valid",
    const std::string &stride = "()",
    const std::string &pad = "()",
    int p_value = 2,
    bool count_include_pad = true) {
  SymbolBuilder builder_("Pooling");
  builder_.SetParam("kernel", kernel);
  builder_.SetParam("pool_type", pool_type);
  builder_.SetParam("global_pool", global_pool ? 1 : 0);
  builder_.SetParam("cudnn_off", cudnn_off ? 1 : 0);
  builder_.SetParam("pooling_convention", pooling_convention);
  builder_.SetParam("stride", stride);
  builder_.SetParam("pad", pad);
  builder_.SetParam("p_value", p_value);
  builder_.SetParam("count_include_pad", count_include_pad ? 1 : 0);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol Pooling_v1(const std::string &symbol_name,
    Symbol data,
    const std::string &kernel = "()",
    const std::string &pool_type = "max",
    bool global_pool = false,
    bool cudnn_off = false,
    const std::string &pooling_convention = "valid",
    const std::string &stride = "()",
    const std::string &pad = "()",
    int p_value = 2,
    bool count_include_pad = true) {
  SymbolBuilder builder_("Pooling_v1");
  builder_.SetParam("kernel", kernel);
  builder_.SetParam("pool_type", pool_type);
  builder_.SetParam("global_pool", global_pool ? 1 : 0);
  builder_.SetParam("cudnn_off", cudnn_off ? 1 : 0);
  builder_.SetParam("pooling_convention", pooling_convention);
  builder_.SetParam("stride", stride);
  builder_.SetParam("pad", pad);
  builder_.SetParam("p_value", p_value);
  builder_.SetParam("count_include_pad", count_include_pad ? 1 : 0);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol Proposal(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    Symbol data2,
    int rpn_pre_nms_top_n = 6000,
    int rpn_post_nms_top_n = 300,
    double threshold = 0.7,
    int rpn_min_size = 16,
    const std::string &scales = "(4, 8, 16, 32)",
    const std::string &ratios = "(0.5, 1, 2)",
    int feature_stride = 16,
    bool output_score = false,
    bool iou_loss = false) {
  SymbolBuilder builder_("Proposal");
  builder_.SetParam("rpn_pre_nms_top_n", rpn_pre_nms_top_n);
  builder_.SetParam("rpn_post_nms_top_n", rpn_post_nms_top_n);
  builder_.SetParam("threshold", threshold);
  builder_.SetParam("rpn_min_size", rpn_min_size);
  builder_.SetParam("scales", scales);
  builder_.SetParam("ratios", ratios);
  builder_.SetParam("feature_stride", feature_stride);
  builder_.SetParam("output_score", output_score ? 1 : 0);
  builder_.SetParam("iou_loss", iou_loss ? 1 : 0);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  builder_.SetInput("data2", data2);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol RMSNorm(const std::string &symbol_name,
    Symbol data,
    Symbol gamma,
    double eps = 1e-05) {
  SymbolBuilder builder_("RMSNorm");
  builder_.SetParam("eps", eps);
  builder_.SetInput("data", data);
  builder_.SetInput("gamma", gamma);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol RNN(const std::string &symbol_name,
    Symbol data,
    Symbol parameters,
    Symbol state,
    Symbol state_cell,
    int state_size,
    int num_layers,
    const std::string &mode,
    bool bidirectional = false,
    double p = 0.0,
    bool state_outputs = false,
    bool lstm_state_clip_nan = false) {
  SymbolBuilder builder_("RNN");
  builder_.SetParam("state_size", state_size);
  builder_.SetParam("num_layers", num_layers);
  builder_.SetParam("mode", mode);
  builder_.SetParam("bidirectional", bidirectional ? 1 : 0);
  builder_.SetParam("p", p);
  builder_.SetParam("state_outputs", state_outputs ? 1 : 0);
  builder_.SetParam("lstm_state_clip_nan", lstm_state_clip_nan ? 1 : 0);
  builder_.SetInput("data", data);
  builder_.SetInput("parameters", parameters);
  builder_.SetInput("state", state);
  builder_.SetInput("state_cell", state_cell);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol ROIAlign(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    const std::string &pooled_size,
    double spatial_scale,
    int sample_ratio = -1) {
  SymbolBuilder builder_("ROIAlign");
  builder_.SetParam("pooled_size", pooled_size);
  builder_.SetParam("spatial_scale", spatial_scale);
  builder_.SetParam("sample_ratio", sample_ratio);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol ROIPooling(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    const std::string &pooled_size,
    double spatial_scale) {
  SymbolBuilder builder_("ROIPooling");
  builder_.SetParam("pooled_size", pooled_size);
  builder_.SetParam("spatial_scale", spatial_scale);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol RadiateSample(const std::string &symbol_name,
    Symbol data,
    const std::string &pad = "(0, 0)",
    int num_group = 1) {
  SymbolBuilder builder_("RadiateSample");
  builder_.SetParam("pad", pad);
  builder_.SetParam("num_group", num_group);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol Reorg(const std::string &symbol_name,
    Symbol data,
    int stride = 2) {
  SymbolBuilder builder_("Reorg");
  builder_.SetParam("stride", stride);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol Reshape(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()",
    bool reverse = false,
    const std::string &target_shape = "()",
    bool keep_highest = false) {
  SymbolBuilder builder_("Reshape");
  builder_.SetParam("shape", shape);
  builder_.SetParam("reverse", reverse ? 1 : 0);
  builder_.SetParam("target_shape", target_shape);
  builder_.SetParam("keep_highest", keep_highest ? 1 : 0);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol SVMOutput(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    double margin = 1.0,
    double regularization_coefficient = 1.0,
    bool use_linear = false) {
  SymbolBuilder builder_("SVMOutput");
  builder_.SetParam("margin", margin);
  builder_.SetParam("regularization_coefficient", regularization_coefficient);
  builder_.SetParam("use_linear", use_linear ? 1 : 0);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol SequenceLast(const std::string &symbol_name,
    Symbol data,
    Symbol sequence_length,
    bool use_sequence_length = false,
    int axis = 0) {
  SymbolBuilder builder_("SequenceLast");
  builder_.SetParam("use_sequence_length", use_sequence_length ? 1 : 0);
  builder_.SetParam("axis", axis);
  builder_.SetInput("data", data);
  builder_.SetInput("sequence_length", sequence_length);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol SequenceMask(const std::string &symbol_name,
    Symbol data,
    Symbol sequence_length,
    bool use_sequence_length = false,
    double value = 0.0,
    int axis = 0) {
  SymbolBuilder builder_("SequenceMask");
  builder_.SetParam("use_sequence_length", use_sequence_length ? 1 : 0);
  builder_.SetParam("value", value);
  builder_.SetParam("axis", axis);
  builder_.SetInput("data", data);
  builder_.SetInput("sequence_length", sequence_length);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol SequenceReverse(const std::string &symbol_name,
    Symbol data,
    Symbol sequence_length,
    bool use_sequence_length = false,
    int axis = 0) {
  SymbolBuilder builder_("SequenceReverse");
  builder_.SetParam("use_sequence_length", use_sequence_length ? 1 : 0);
  builder_.SetParam("axis", axis);
  builder_.SetInput("data", data);
  builder_.SetInput("sequence_length", sequence_length);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol ShortConv(const std::string &symbol_name,
    Symbol data,
    Symbol in_proj_weight,
    Symbol conv_weight,
    Symbol out_proj_weight,
    int kernel = 3) {
  SymbolBuilder builder_("ShortConv");
  builder_.SetParam("kernel", kernel);
  builder_.SetInput("data", data);
  builder_.SetInput("in_proj_weight", in_proj_weight);
  builder_.SetInput("conv_weight", conv_weight);
  builder_.SetInput("out_proj_weight", out_proj_weight);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol SliceChannel(const std::string &symbol_name,
    Symbol data,
    int num_outputs,
    int axis = 1,
    bool squeeze_axis = false) {
  SymbolBuilder builder_("SliceChannel");
  builder_.SetParam("num_outputs", num_outputs);
  builder_.SetParam("axis", axis);
  builder_.SetParam("squeeze_axis", squeeze_axis ? 1 : 0);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol Softmax(const std::string &symbol_name,
    Symbol data,
    Symbol label,
    double grad_scale = 1.0,
    double ignore_label = -1.0,
    bool multi_output = false,
    bool use_ignore = false,
    bool preserve_shape = false,
    const std::string &normalization = "null",
    bool out_grad = false,
    double smooth_alpha = 0.0) {
  SymbolBuilder builder_("Softmax");
  builder_.SetParam("grad_scale", grad_scale);
  builder_.SetParam("ignore_label", ignore_label);
  builder_.SetParam("multi_output", multi_output ? 1 : 0);
  builder_.SetParam("use_ignore", use_ignore ? 1 : 0);
  builder_.SetParam("preserve_shape", preserve_shape ? 1 : 0);
  builder_.SetParam("normalization", normalization);
  builder_.SetParam("out_grad", out_grad ? 1 : 0);
  builder_.SetParam("smooth_alpha", smooth_alpha);
  builder_.SetInput("data", data);
  builder_.SetInput("label", label);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol SoftmaxActivation(const std::string &symbol_name,
    Symbol data,
    const std::string &mode = "instance") {
  SymbolBuilder builder_("SoftmaxActivation");
  builder_.SetParam("mode", mode);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol SoftmaxOutput(const std::string &symbol_name,
    Symbol data,
    Symbol label,
    double grad_scale = 1.0,
    double ignore_label = -1.0,
    bool multi_output = false,
    bool use_ignore = false,
    bool preserve_shape = false,
    const std::string &normalization = "null",
    bool out_grad = false,
    double smooth_alpha = 0.0) {
  SymbolBuilder builder_("SoftmaxOutput");
  builder_.SetParam("grad_scale", grad_scale);
  builder_.SetParam("ignore_label", ignore_label);
  builder_.SetParam("multi_output", multi_output ? 1 : 0);
  builder_.SetParam("use_ignore", use_ignore ? 1 : 0);
  builder_.SetParam("preserve_shape", preserve_shape ? 1 : 0);
  builder_.SetParam("normalization", normalization);
  builder_.SetParam("out_grad", out_grad ? 1 : 0);
  builder_.SetParam("smooth_alpha", smooth_alpha);
  builder_.SetInput("data", data);
  builder_.SetInput("label", label);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol SparseMoE(const std::string &symbol_name,
    Symbol data,
    Symbol router_weight,
    Symbol expert_bias,
    Symbol expert_gate_weight,
    Symbol expert_up_weight,
    Symbol expert_down_weight,
    Symbol expert_load,
    int num_experts,
    int num_experts_per_tok,
    int num_hidden,
    int num_held = 0,
    int expert_offset = 0,
    const std::string &score = "sigmoid",
    double routed_scaling = 1.0,
    double weight_eps = 1e-06) {
  SymbolBuilder builder_("SparseMoE");
  builder_.SetParam("num_experts", num_experts);
  builder_.SetParam("num_experts_per_tok", num_experts_per_tok);
  builder_.SetParam("num_hidden", num_hidden);
  builder_.SetParam("num_held", num_held);
  builder_.SetParam("expert_offset", expert_offset);
  builder_.SetParam("score", score);
  builder_.SetParam("routed_scaling", routed_scaling);
  builder_.SetParam("weight_eps", weight_eps);
  builder_.SetInput("data", data);
  builder_.SetInput("router_weight", router_weight);
  builder_.SetInput("expert_bias", expert_bias);
  builder_.SetInput("expert_gate_weight", expert_gate_weight);
  builder_.SetInput("expert_up_weight", expert_up_weight);
  builder_.SetInput("expert_down_weight", expert_down_weight);
  builder_.SetInput("expert_load", expert_load);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol SpatialTransformer(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    const std::string &target_shape = "(0, 0)",
    const std::string &transform_type = "affine",
    const std::string &sampler_type = "bilinear") {
  SymbolBuilder builder_("SpatialTransformer");
  builder_.SetParam("target_shape", target_shape);
  builder_.SetParam("transform_type", transform_type);
  builder_.SetParam("sampler_type", sampler_type);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol SwapAxis(const std::string &symbol_name,
    Symbol data,
    int dim1 = 0,
    int dim2 = 0) {
  SymbolBuilder builder_("SwapAxis");
  builder_.SetParam("dim1", dim1);
  builder_.SetParam("dim2", dim2);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol UpSampling(const std::string &symbol_name,
    const std::vector<Symbol> &data,
    int scale,
    int num_filter = 0,
    const std::string &sample_type = "nearest",
    const std::string &multi_input_mode = "concat",
    int workspace = 512) {
  SymbolBuilder builder_("UpSampling");
  builder_.SetParam("scale", scale);
  builder_.SetParam("num_filter", num_filter);
  builder_.SetParam("sample_type", sample_type);
  builder_.SetParam("multi_input_mode", multi_input_mode);
  builder_.SetParam("workspace", workspace);
  builder_.SetParam("num_args", data.size());
  for (const auto &s : data) builder_.AddInput(s);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _add(const std::string &symbol_name,
    Symbol lhs,
    Symbol rhs) {
  SymbolBuilder builder_("_add");
  builder_.SetInput("lhs", lhs);
  builder_.SetInput("rhs", rhs);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _arange(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()",
    const std::string &dtype = "float32",
    double start = 0.0,
    double step = 1.0,
    int repeat = 1,
    bool infer_range = false) {
  SymbolBuilder builder_("_arange");
  builder_.SetParam("shape", shape);
  builder_.SetParam("dtype", dtype);
  builder_.SetParam("start", start);
  builder_.SetParam("step", step);
  builder_.SetParam("repeat", repeat);
  builder_.SetParam("infer_range", infer_range ? 1 : 0);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _cast_storage(const std::string &symbol_name,
    Symbol data,
    const std::string &stype = "default") {
  SymbolBuilder builder_("_cast_storage");
  builder_.SetParam("stype", stype);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _contrib_AdaptiveAvgPooling2D(const std::string &symbol_name,
    Symbol data,
    const std::string &output_size = "()") {
  SymbolBuilder builder_("_contrib_AdaptiveAvgPooling2D");
  builder_.SetParam("output_size", output_size);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _contrib_BilinearResize2D(const std::string &symbol_name,
    Symbol data,
    int height,
    int width) {
  SymbolBuilder builder_("_contrib_BilinearResize2D");
  builder_.SetParam("height", height);
  builder_.SetParam("width", width);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _contrib_CTCLoss(const std::string &symbol_name,
    const std::vector<Symbol> &data,
    bool use_data_lengths = false,
    bool use_label_lengths = false,
    const std::string &blank_label = "first") {
  SymbolBuilder builder_("_contrib_CTCLoss");
  builder_.SetParam("use_data_lengths", use_data_lengths ? 1 : 0);
  builder_.SetParam("use_label_lengths", use_label_lengths ? 1 : 0);
  builder_.SetParam("blank_label", blank_label);
  for (const auto &s : data) builder_.AddInput(s);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _contrib_ChannelOperator(const std::string &symbol_name,
    const std::vector<Symbol> &data,
    const std::string &op_type,
    int group,
    const std::string &pick_type = "Label_Pick") {
  SymbolBuilder builder_("_contrib_ChannelOperator");
  builder_.SetParam("op_type", op_type);
  builder_.SetParam("group", group);
  builder_.SetParam("pick_type", pick_type);
  for (const auto &s : data) builder_.AddInput(s);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _contrib_DeformableConvolution(const std::string &symbol_name,
    const std::vector<Symbol> &data,
    const std::string &kernel,
    int num_filter,
    const std::string &stride = "()",
    const std::string &dilate = "()",
    const std::string &pad = "()",
    int num_group = 1,
    int num_deformable_group = 1,
    int workspace = 1024,
    bool no_bias = false) {
  SymbolBuilder builder_("_contrib_DeformableConvolution");
  builder_.SetParam("kernel", kernel);
  builder_.SetParam("num_filter", num_filter);
  builder_.SetParam("stride", stride);
  builder_.SetParam("dilate", dilate);
  builder_.SetParam("pad", pad);
  builder_.SetParam("num_group", num_group);
  builder_.SetParam("num_deformable_group", num_deformable_group);
  builder_.SetParam("workspace", workspace);
  builder_.SetParam("no_bias", no_bias ? 1 : 0);
  for (const auto &s : data) builder_.AddInput(s);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _contrib_DeformablePSROIPooling(const std::string &symbol_name,
    const std::vector<Symbol> &data,
    double spatial_scale,
    int output_dim,
    int group_size,
    int pooled_size,
    int part_size = 0,
    int sample_per_part = 1,
    double trans_std = 0.0,
    bool no_trans = false) {
  SymbolBuilder builder_("_contrib_DeformablePSROIPooling");
  builder_.SetParam("spatial_scale", spatial_scale);
  builder_.SetParam("output_dim", output_dim);
  builder_.SetParam("group_size", group_size);
  builder_.SetParam("pooled_size", pooled_size);
  builder_.SetParam("part_size", part_size);
  builder_.SetParam("sample_per_part", sample_per_part);
  builder_.SetParam("trans_std", trans_std);
  builder_.SetParam("no_trans", no_trans ? 1 : 0);
  for (const auto &s : data) builder_.AddInput(s);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _contrib_MultiBoxDetection(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    Symbol data2,
    bool clip = true,
    double threshold = 0.01,
    int background_id = 0,
    double nms_threshold = 0.5,
    bool force_suppress = false,
    const std::string &variances = "(0.1, 0.1, 0.2, 0.2)",
    int nms_topk = -1) {
  SymbolBuilder builder_("_contrib_MultiBoxDetection");
  builder_.SetParam("clip", clip ? 1 : 0);
  builder_.SetParam("threshold", threshold);
  builder_.SetParam("background_id", background_id);
  builder_.SetParam("nms_threshold", nms_threshold);
  builder_.SetParam("force_suppress", force_suppress ? 1 : 0);
  builder_.SetParam("variances", variances);
  builder_.SetParam("nms_topk", nms_topk);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  builder_.SetInput("data2", data2);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _contrib_MultiBoxPrior(const std::string &symbol_name,
    Symbol data,
    const std::string &sizes = "(1)",
    const std::string &ratios = "(1)",
    bool clip = false,
    const std::string &steps = "(-1, -1)",
    const std::string &offsets = "(0.5, 0.5)") {
  SymbolBuilder builder_("_contrib_MultiBoxPrior");
  builder_.SetParam("sizes", sizes);
  builder_.SetParam("ratios", ratios);
  builder_.SetParam("clip", clip ? 1 : 0);
  builder_.SetParam("steps", steps);
  builder_.SetParam("offsets", offsets);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _contrib_MultiBoxTarget(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    Symbol data2,
    double overlap_threshold = 0.5,
    double ignore_label = -1.0,
    double negative_mining_ratio = -1.0,
    double negative_mining_thresh = 0.5,
    int minimum_negative_samples = 0,
    const std::string &variances = "(0.1, 0.1, 0.2, 0.2)") {
  SymbolBuilder builder_("_contrib_MultiBoxTarget");
  builder_.SetParam("overlap_threshold", overlap_threshold);
  builder_.SetParam("ignore_label", ignore_label);
  builder_.SetParam("negative_mining_ratio", negative_mining_ratio);
  builder_.SetParam("negative_mining_thresh", negative_mining_thresh);
  builder_.SetParam("minimum_negative_samples", minimum_negative_samples);
  builder_.SetParam("variances", variances);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  builder_.SetInput("data2", data2);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _contrib_MultiProposal(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    Symbol data2,
    int rpn_pre_nms_top_n = 6000,
    int rpn_post_nms_top_n = 300,
    double threshold = 0.7,
    int rpn_min_size = 16,
    const std::string &scales = "(4, 8, 16, 32)",
    const std::string &ratios = "(0.5, 1, 2)",
    int feature_stride = 16,
    bool output_score = false,
    bool iou_loss = false) {
  SymbolBuilder builder_("_contrib_MultiProposal");
  builder_.SetParam("rpn_pre_nms_top_n", rpn_pre_nms_top_n);
  builder_.SetParam("rpn_post_nms_top_n", rpn_post_nms_top_n);
  builder_.SetParam("threshold", threshold);
  builder_.SetParam("rpn_min_size", rpn_min_size);
  builder_.SetParam("scales", scales);
  builder_.SetParam("ratios", ratios);
  builder_.SetParam("feature_stride", feature_stride);
  builder_.SetParam("output_score", output_score ? 1 : 0);
  builder_.SetParam("iou_loss", iou_loss ? 1 : 0);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  builder_.SetInput("data2", data2);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _contrib_PSROIPooling(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    double spatial_scale,
    int output_dim,
    int pooled_size,
    int group_size = 0) {
  SymbolBuilder builder_("_contrib_PSROIPooling");
  builder_.SetParam("spatial_scale", spatial_scale);
  builder_.SetParam("output_dim", output_dim);
  builder_.SetParam("pooled_size", pooled_size);
  builder_.SetParam("group_size", group_size);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _contrib_Proposal(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    Symbol data2,
    int rpn_pre_nms_top_n = 6000,
    int rpn_post_nms_top_n = 300,
    double threshold = 0.7,
    int rpn_min_size = 16,
    const std::string &scales = "(4, 8, 16, 32)",
    const std::string &ratios = "(0.5, 1, 2)",
    int feature_stride = 16,
    bool output_score = false,
    bool iou_loss = false) {
  SymbolBuilder builder_("_contrib_Proposal");
  builder_.SetParam("rpn_pre_nms_top_n", rpn_pre_nms_top_n);
  builder_.SetParam("rpn_post_nms_top_n", rpn_post_nms_top_n);
  builder_.SetParam("threshold", threshold);
  builder_.SetParam("rpn_min_size", rpn_min_size);
  builder_.SetParam("scales", scales);
  builder_.SetParam("ratios", ratios);
  builder_.SetParam("feature_stride", feature_stride);
  builder_.SetParam("output_score", output_score ? 1 : 0);
  builder_.SetParam("iou_loss", iou_loss ? 1 : 0);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  builder_.SetInput("data2", data2);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _contrib_ROIAlign(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    const std::string &pooled_size,
    double spatial_scale,
    int sample_ratio = -1) {
  SymbolBuilder builder_("_contrib_ROIAlign");
  builder_.SetParam("pooled_size", pooled_size);
  builder_.SetParam("spatial_scale", spatial_scale);
  builder_.SetParam("sample_ratio", sample_ratio);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _contrib_SparseEmbedding(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    int input_dim,
    int output_dim,
    const std::string &dtype = "float32") {
  SymbolBuilder builder_("_contrib_SparseEmbedding");
  builder_.SetParam("input_dim", input_dim);
  builder_.SetParam("output_dim", output_dim);
  builder_.SetParam("dtype", dtype);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _contrib_bipartite_matching(const std::string &symbol_name,
    Symbol data,
    double threshold,
    bool is_ascend = false,
    int topk = -1) {
  SymbolBuilder builder_("_contrib_bipartite_matching");
  builder_.SetParam("threshold", threshold);
  builder_.SetParam("is_ascend", is_ascend ? 1 : 0);
  builder_.SetParam("topk", topk);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _contrib_box_iou(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    const std::string &format = "corner") {
  SymbolBuilder builder_("_contrib_box_iou");
  builder_.SetParam("format", format);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _contrib_box_nms(const std::string &symbol_name,
    Symbol data,
    double overlap_thresh = 0.5,
    double valid_thresh = 0.0,
    int topk = -1,
    int coord_start = 2,
    int score_index = 1,
    int id_index = -1,
    bool force_suppress = false,
    const std::string &in_format = "corner",
    const std::string &out_format = "corner") {
  SymbolBuilder builder_("_contrib_box_nms");
  builder_.SetParam("overlap_thresh", overlap_thresh);
  builder_.SetParam("valid_thresh", valid_thresh);
  builder_.SetParam("topk", topk);
  builder_.SetParam("coord_start", coord_start);
  builder_.SetParam("score_index", score_index);
  builder_.SetParam("id_index", id_index);
  builder_.SetParam("force_suppress", force_suppress ? 1 : 0);
  builder_.SetParam("in_format", in_format);
  builder_.SetParam("out_format", out_format);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _contrib_box_non_maximum_suppression(const std::string &symbol_name,
    Symbol data,
    double overlap_thresh = 0.5,
    double valid_thresh = 0.0,
    int topk = -1,
    int coord_start = 2,
    int score_index = 1,
    int id_index = -1,
    bool force_suppress = false,
    const std::string &in_format = "corner",
    const std::string &out_format = "corner") {
  SymbolBuilder builder_("_contrib_box_non_maximum_suppression");
  builder_.SetParam("overlap_thresh", overlap_thresh);
  builder_.SetParam("valid_thresh", valid_thresh);
  builder_.SetParam("topk", topk);
  builder_.SetParam("coord_start", coord_start);
  builder_.SetParam("score_index", score_index);
  builder_.SetParam("id_index", id_index);
  builder_.SetParam("force_suppress", force_suppress ? 1 : 0);
  builder_.SetParam("in_format", in_format);
  builder_.SetParam("out_format", out_format);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _contrib_count_sketch(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    Symbol data2,
    int out_dim,
    int processing_batch_size = 32) {
  SymbolBuilder builder_("_contrib_count_sketch");
  builder_.SetParam("out_dim", out_dim);
  builder_.SetParam("processing_batch_size", processing_batch_size);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  builder_.SetInput("data2", data2);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _contrib_ctc_loss(const std::string &symbol_name,
    const std::vector<Symbol> &data,
    bool use_data_lengths = false,
    bool use_label_lengths = false,
    const std::string &blank_label = "first") {
  SymbolBuilder builder_("_contrib_ctc_loss");
  builder_.SetParam("use_data_lengths", use_data_lengths ? 1 : 0);
  builder_.SetParam("use_label_lengths", use_label_lengths ? 1 : 0);
  builder_.SetParam("blank_label", blank_label);
  for (const auto &s : data) builder_.AddInput(s);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _contrib_dequantize(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    Symbol data2,
    const std::string &out_type = "float32") {
  SymbolBuilder builder_("_contrib_dequantize");
  builder_.SetParam("out_type", out_type);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  builder_.SetInput("data2", data2);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _contrib_dequantize_v2(const std::string &symbol_name,
    Symbol data,
    double threshold) {
  SymbolBuilder builder_("_contrib_dequantize_v2");
  builder_.SetParam("threshold", threshold);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _contrib_div_sqrt_dim(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("_contrib_div_sqrt_dim");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _contrib_fft(const std::string &symbol_name,
    Symbol data,
    int compute_size = 128) {
  SymbolBuilder builder_("_contrib_fft");
  builder_.SetParam("compute_size", compute_size);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _contrib_ifft(const std::string &symbol_name,
    Symbol data,
    int compute_size = 128) {
  SymbolBuilder builder_("_contrib_ifft");
  builder_.SetParam("compute_size", compute_size);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _contrib_quadratic(const std::string &symbol_name,
    Symbol data,
    double a = 0.0,
    double b = 0.0,
    double c = 0.0) {
  SymbolBuilder builder_("_contrib_quadratic");
  builder_.SetParam("a", a);
  builder_.SetParam("b", b);
  builder_.SetParam("c", c);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _contrib_quantize(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    Symbol data2,
    const std::string &out_type = "int8") {
  SymbolBuilder builder_("_contrib_quantize");
  builder_.SetParam("out_type", out_type);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  builder_.SetInput("data2", data2);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _contrib_quantize_v2(const std::string &symbol_name,
    Symbol data,
    const std::string &out_type = "int8") {
  SymbolBuilder builder_("_contrib_quantize_v2");
  builder_.SetParam("out_type", out_type);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _contrib_quantized_conv(const std::string &symbol_name,
    const std::vector<Symbol> &data,
    const std::string &kernel,
    int num_filter,
    const std::string &stride = "()",
    const std::string &dilate = "()",
    const std::string &pad = "()",
    int num_group = 1,
    bool no_bias = false) {
  SymbolBuilder builder_("_contrib_quantized_conv");
  builder_.SetParam("kernel", kernel);
  builder_.SetParam("num_filter", num_filter);
  builder_.SetParam("stride", stride);
  builder_.SetParam("dilate", dilate);
  builder_.SetParam("pad", pad);
  builder_.SetParam("num_group", num_group);
  builder_.SetParam("no_bias", no_bias ? 1 : 0);
  for (const auto &s : data) builder_.AddInput(s);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _contrib_quantized_flatten(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    Symbol data2) {
  SymbolBuilder builder_("_contrib_quantized_flatten");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  builder_.SetInput("data2", data2);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _contrib_quantized_fully_connected(const std::string &symbol_name,
    const std::vector<Symbol> &data,
    int num_hidden,
    bool no_bias = false,
    bool flatten = true) {
  SymbolBuilder builder_("_contrib_quantized_fully_connected");
  builder_.SetParam("num_hidden", num_hidden);
  builder_.SetParam("no_bias", no_bias ? 1 : 0);
  builder_.SetParam("flatten", flatten ? 1 : 0);
  for (const auto &s : data) builder_.AddInput(s);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _contrib_quantized_pooling(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    Symbol data2,
    const std::string &kernel = "()",
    const std::string &pool_type = "max",
    bool global_pool = false,
    const std::string &stride = "()",
    const std::string &pad = "()",
    const std::string &pooling_convention = "valid",
    bool count_include_pad = true,
    int p_value = 2) {
  SymbolBuilder builder_("_contrib_quantized_pooling");
  builder_.SetParam("kernel", kernel);
  builder_.SetParam("pool_type", pool_type);
  builder_.SetParam("global_pool", global_pool ? 1 : 0);
  builder_.SetParam("stride", stride);
  builder_.SetParam("pad", pad);
  builder_.SetParam("pooling_convention", pooling_convention);
  builder_.SetParam("count_include_pad", count_include_pad ? 1 : 0);
  builder_.SetParam("p_value", p_value);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  builder_.SetInput("data2", data2);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _contrib_requantize(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    Symbol data2) {
  SymbolBuilder builder_("_contrib_requantize");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  builder_.SetInput("data2", data2);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _copy(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("_copy");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _div(const std::string &symbol_name,
    Symbol lhs,
    Symbol rhs) {
  SymbolBuilder builder_("_div");
  builder_.SetInput("lhs", lhs);
  builder_.SetInput("rhs", rhs);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _div_scalar(const std::string &symbol_name,
    Symbol data,
    double scalar = 0.0) {
  SymbolBuilder builder_("_div_scalar");
  builder_.SetParam("scalar", scalar);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _equal(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("_equal");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _equal_scalar(const std::string &symbol_name,
    Symbol data,
    double scalar = 0.0) {
  SymbolBuilder builder_("_equal_scalar");
  builder_.SetParam("scalar", scalar);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _eye(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()",
    const std::string &dtype = "float32",
    int N = 0,
    int M = 0,
    int k = 0) {
  SymbolBuilder builder_("_eye");
  builder_.SetParam("shape", shape);
  builder_.SetParam("dtype", dtype);
  builder_.SetParam("N", N);
  builder_.SetParam("M", M);
  builder_.SetParam("k", k);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _foreach(const std::string &symbol_name,
    const std::vector<Symbol> &data,
    int num_data = 1,
    int num_states = 0,
    int num_out_data = 1,
    int num_outputs = 1) {
  SymbolBuilder builder_("_foreach");
  builder_.SetParam("num_data", num_data);
  builder_.SetParam("num_states", num_states);
  builder_.SetParam("num_out_data", num_out_data);
  builder_.SetParam("num_outputs", num_outputs);
  for (const auto &s : data) builder_.AddInput(s);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _full(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()",
    const std::string &dtype = "float32",
    double value = 0.0) {
  SymbolBuilder builder_("_full");
  builder_.SetParam("shape", shape);
  builder_.SetParam("dtype", dtype);
  builder_.SetParam("value", value);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _grad_add(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("_grad_add");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _greater(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("_greater");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _greater_equal(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("_greater_equal");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _greater_equal_scalar(const std::string &symbol_name,
    Symbol data,
    double scalar = 0.0) {
  SymbolBuilder builder_("_greater_equal_scalar");
  builder_.SetParam("scalar", scalar);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _greater_scalar(const std::string &symbol_name,
    Symbol data,
    double scalar = 0.0) {
  SymbolBuilder builder_("_greater_scalar");
  builder_.SetParam("scalar", scalar);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _histogram(const std::string &symbol_name,
    const std::vector<Symbol> &data) {
  SymbolBuilder builder_("_histogram");
  for (const auto &s : data) builder_.AddInput(s);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _hypot(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("_hypot");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _hypot_scalar(const std::string &symbol_name,
    Symbol data,
    double scalar = 0.0) {
  SymbolBuilder builder_("_hypot_scalar");
  builder_.SetParam("scalar", scalar);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _identity_with_attr_like_rhs(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("_identity_with_attr_like_rhs");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _image_normalize(const std::string &symbol_name,
    Symbol data,
    const std::string &mean = "(0)",
    const std::string &std = "(1)") {
  SymbolBuilder builder_("_image_normalize");
  builder_.SetParam("mean", mean);
  builder_.SetParam("std", std);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _image_to_tensor(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("_image_to_tensor");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _lesser(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("_lesser");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _lesser_equal(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("_lesser_equal");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _lesser_equal_scalar(const std::string &symbol_name,
    Symbol data,
    double scalar = 0.0) {
  SymbolBuilder builder_("_lesser_equal_scalar");
  builder_.SetParam("scalar", scalar);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _lesser_scalar(const std::string &symbol_name,
    Symbol data,
    double scalar = 0.0) {
  SymbolBuilder builder_("_lesser_scalar");
  builder_.SetParam("scalar", scalar);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _linalg_gelqf(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("_linalg_gelqf");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _linalg_gemm(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    Symbol data2,
    bool transpose_a = false,
    bool transpose_b = false,
    double alpha = 1.0,
    double beta = 1.0,
    int axis = -3) {
  SymbolBuilder builder_("_linalg_gemm");
  builder_.SetParam("transpose_a", transpose_a ? 1 : 0);
  builder_.SetParam("transpose_b", transpose_b ? 1 : 0);
  builder_.SetParam("alpha", alpha);
  builder_.SetParam("beta", beta);
  builder_.SetParam("axis", axis);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  builder_.SetInput("data2", data2);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _linalg_gemm2(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    bool transpose_a = false,
    bool transpose_b = false,
    double alpha = 1.0,
    int axis = -3) {
  SymbolBuilder builder_("_linalg_gemm2");
  builder_.SetParam("transpose_a", transpose_a ? 1 : 0);
  builder_.SetParam("transpose_b", transpose_b ? 1 : 0);
  builder_.SetParam("alpha", alpha);
  builder_.SetParam("axis", axis);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _linalg_potrf(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("_linalg_potrf");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _linalg_potri(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("_linalg_potri");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _linalg_sumlogdiag(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("_linalg_sumlogdiag");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _linalg_syevd(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("_linalg_syevd");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _linalg_syrk(const std::string &symbol_name,
    Symbol data,
    bool transpose = false,
    double alpha = 1.0) {
  SymbolBuilder builder_("_linalg_syrk");
  builder_.SetParam("transpose", transpose ? 1 : 0);
  builder_.SetParam("alpha", alpha);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _linalg_trmm(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    bool transpose = false,
    bool rightside = false,
    double alpha = 1.0) {
  SymbolBuilder builder_("_linalg_trmm");
  builder_.SetParam("transpose", transpose ? 1 : 0);
  builder_.SetParam("rightside", rightside ? 1 : 0);
  builder_.SetParam("alpha", alpha);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _linalg_trsm(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    bool transpose = false,
    bool rightside = false,
    double alpha = 1.0) {
  SymbolBuilder builder_("_linalg_trsm");
  builder_.SetParam("transpose", transpose ? 1 : 0);
  builder_.SetParam("rightside", rightside ? 1 : 0);
  builder_.SetParam("alpha", alpha);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _linspace(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()",
    const std::string &dtype = "float32",
    double start = 0.0,
    double stop = 1.0,
    int num = 50,
    bool endpoint = true) {
  SymbolBuilder builder_("_linspace");
  builder_.SetParam("shape", shape);
  builder_.SetParam("dtype", dtype);
  builder_.SetParam("start", start);
  builder_.SetParam("stop", stop);
  builder_.SetParam("num", num);
  builder_.SetParam("endpoint", endpoint ? 1 : 0);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _logical_and(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("_logical_and");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _logical_and_scalar(const std::string &symbol_name,
    Symbol data,
    double scalar = 0.0) {
  SymbolBuilder builder_("_logical_and_scalar");
  builder_.SetParam("scalar", scalar);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _logical_or(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("_logical_or");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _logical_or_scalar(const std::string &symbol_name,
    Symbol data,
    double scalar = 0.0) {
  SymbolBuilder builder_("_logical_or_scalar");
  builder_.SetParam("scalar", scalar);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _logical_xor(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("_logical_xor");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _logical_xor_scalar(const std::string &symbol_name,
    Symbol data,
    double scalar = 0.0) {
  SymbolBuilder builder_("_logical_xor_scalar");
  builder_.SetParam("scalar", scalar);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _maximum(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("_maximum");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _maximum_scalar(const std::string &symbol_name,
    Symbol data,
    double scalar = 0.0) {
  SymbolBuilder builder_("_maximum_scalar");
  builder_.SetParam("scalar", scalar);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _minimum(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("_minimum");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _minimum_scalar(const std::string &symbol_name,
    Symbol data,
    double scalar = 0.0) {
  SymbolBuilder builder_("_minimum_scalar");
  builder_.SetParam("scalar", scalar);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _minus(const std::string &symbol_name,
    Symbol lhs,
    Symbol rhs) {
  SymbolBuilder builder_("_minus");
  builder_.SetInput("lhs", lhs);
  builder_.SetInput("rhs", rhs);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _minus_scalar(const std::string &symbol_name,
    Symbol data,
    double scalar = 0.0) {
  SymbolBuilder builder_("_minus_scalar");
  builder_.SetParam("scalar", scalar);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _mod(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("_mod");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _mod_scalar(const std::string &symbol_name,
    Symbol data,
    double scalar = 0.0) {
  SymbolBuilder builder_("_mod_scalar");
  builder_.SetParam("scalar", scalar);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _mul(const std::string &symbol_name,
    Symbol lhs,
    Symbol rhs) {
  SymbolBuilder builder_("_mul");
  builder_.SetInput("lhs", lhs);
  builder_.SetInput("rhs", rhs);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _mul_scalar(const std::string &symbol_name,
    Symbol data,
    double scalar = 0.0) {
  SymbolBuilder builder_("_mul_scalar");
  builder_.SetParam("scalar", scalar);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _not_equal(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("_not_equal");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _not_equal_scalar(const std::string &symbol_name,
    Symbol data,
    double scalar = 0.0) {
  SymbolBuilder builder_("_not_equal_scalar");
  builder_.SetParam("scalar", scalar);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _ones(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()",
    const std::string &dtype = "float32") {
  SymbolBuilder builder_("_ones");
  builder_.SetParam("shape", shape);
  builder_.SetParam("dtype", dtype);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _plus(const std::string &symbol_name,
    Symbol lhs,
    Symbol rhs) {
  SymbolBuilder builder_("_plus");
  builder_.SetInput("lhs", lhs);
  builder_.SetInput("rhs", rhs);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _plus_scalar(const std::string &symbol_name,
    Symbol data,
    double scalar = 0.0) {
  SymbolBuilder builder_("_plus_scalar");
  builder_.SetParam("scalar", scalar);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _pow(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("_pow");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _power(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("_power");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _power_scalar(const std::string &symbol_name,
    Symbol data,
    double scalar = 0.0) {
  SymbolBuilder builder_("_power_scalar");
  builder_.SetParam("scalar", scalar);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _random_exponential(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()",
    double lam = 1.0) {
  SymbolBuilder builder_("_random_exponential");
  builder_.SetParam("shape", shape);
  builder_.SetParam("lam", lam);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _random_gamma(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()",
    double alpha = 1.0,
    double beta = 1.0) {
  SymbolBuilder builder_("_random_gamma");
  builder_.SetParam("shape", shape);
  builder_.SetParam("alpha", alpha);
  builder_.SetParam("beta", beta);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _random_generalized_negative_binomial(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()",
    double mu = 1.0,
    double alpha = 1.0) {
  SymbolBuilder builder_("_random_generalized_negative_binomial");
  builder_.SetParam("shape", shape);
  builder_.SetParam("mu", mu);
  builder_.SetParam("alpha", alpha);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _random_negative_binomial(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()",
    int k = 1,
    double p = 1.0) {
  SymbolBuilder builder_("_random_negative_binomial");
  builder_.SetParam("shape", shape);
  builder_.SetParam("k", k);
  builder_.SetParam("p", p);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _random_normal(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()",
    double loc = 0.0,
    double scale = 1.0) {
  SymbolBuilder builder_("_random_normal");
  builder_.SetParam("shape", shape);
  builder_.SetParam("loc", loc);
  builder_.SetParam("scale", scale);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _random_poisson(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()",
    double lam = 1.0) {
  SymbolBuilder builder_("_random_poisson");
  builder_.SetParam("shape", shape);
  builder_.SetParam("lam", lam);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _random_randint(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()",
    int low = 0,
    int high = 1) {
  SymbolBuilder builder_("_random_randint");
  builder_.SetParam("shape", shape);
  builder_.SetParam("low", low);
  builder_.SetParam("high", high);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _random_uniform(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()",
    double low = 0.0,
    double high = 1.0) {
  SymbolBuilder builder_("_random_uniform");
  builder_.SetParam("shape", shape);
  builder_.SetParam("low", low);
  builder_.SetParam("high", high);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _ravel_multi_index(const std::string &symbol_name,
    Symbol data,
    const std::string &shape) {
  SymbolBuilder builder_("_ravel_multi_index");
  builder_.SetParam("shape", shape);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _rdiv_scalar(const std::string &symbol_name,
    Symbol data,
    double scalar = 0.0) {
  SymbolBuilder builder_("_rdiv_scalar");
  builder_.SetParam("scalar", scalar);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _rminus_scalar(const std::string &symbol_name,
    Symbol data,
    double scalar = 0.0) {
  SymbolBuilder builder_("_rminus_scalar");
  builder_.SetParam("scalar", scalar);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _rmod_scalar(const std::string &symbol_name,
    Symbol data,
    double scalar = 0.0) {
  SymbolBuilder builder_("_rmod_scalar");
  builder_.SetParam("scalar", scalar);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _rpower_scalar(const std::string &symbol_name,
    Symbol data,
    double scalar = 0.0) {
  SymbolBuilder builder_("_rpower_scalar");
  builder_.SetParam("scalar", scalar);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _sample_exponential(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()") {
  SymbolBuilder builder_("_sample_exponential");
  builder_.SetParam("shape", shape);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _sample_gamma(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    const std::string &shape = "()") {
  SymbolBuilder builder_("_sample_gamma");
  builder_.SetParam("shape", shape);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _sample_generalized_negative_binomial(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    const std::string &shape = "()") {
  SymbolBuilder builder_("_sample_generalized_negative_binomial");
  builder_.SetParam("shape", shape);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _sample_multinomial(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()",
    bool get_prob = false,
    const std::string &dtype = "int32") {
  SymbolBuilder builder_("_sample_multinomial");
  builder_.SetParam("shape", shape);
  builder_.SetParam("get_prob", get_prob ? 1 : 0);
  builder_.SetParam("dtype", dtype);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _sample_negative_binomial(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    const std::string &shape = "()") {
  SymbolBuilder builder_("_sample_negative_binomial");
  builder_.SetParam("shape", shape);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _sample_normal(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    const std::string &shape = "()") {
  SymbolBuilder builder_("_sample_normal");
  builder_.SetParam("shape", shape);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _sample_poisson(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()") {
  SymbolBuilder builder_("_sample_poisson");
  builder_.SetParam("shape", shape);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _sample_uniform(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    const std::string &shape = "()") {
  SymbolBuilder builder_("_sample_uniform");
  builder_.SetParam("shape", shape);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _scatter_elemwise_div(const std::string &symbol_name,
    Symbol lhs,
    Symbol rhs) {
  SymbolBuilder builder_("_scatter_elemwise_div");
  builder_.SetInput("lhs", lhs);
  builder_.SetInput("rhs", rhs);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _scatter_minus_scalar(const std::string &symbol_name,
    Symbol data,
    double scalar = 0.0) {
  SymbolBuilder builder_("_scatter_minus_scalar");
  builder_.SetParam("scalar", scalar);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _scatter_plus_scalar(const std::string &symbol_name,
    Symbol data,
    double scalar = 0.0) {
  SymbolBuilder builder_("_scatter_plus_scalar");
  builder_.SetParam("scalar", scalar);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _scatter_set_nd(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    Symbol data2,
    const std::string &shape) {
  SymbolBuilder builder_("_scatter_set_nd");
  builder_.SetParam("shape", shape);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  builder_.SetInput("data2", data2);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _sg_int8_conv(const std::string &symbol_name,
    const std::vector<Symbol> &data,
    const std::string &kernel,
    int num_filter,
    double scale_out,
    const std::string &stride = "()",
    const std::string &dilate = "()",
    const std::string &pad = "()",
    int num_group = 1,
    bool no_bias = false,
    bool fuse_relu = false,
    bool dequant_out = false) {
  SymbolBuilder builder_("_sg_int8_conv");
  builder_.SetParam("kernel", kernel);
  builder_.SetParam("num_filter", num_filter);
  builder_.SetParam("scale_out", scale_out);
  builder_.SetParam("stride", stride);
  builder_.SetParam("dilate", dilate);
  builder_.SetParam("pad", pad);
  builder_.SetParam("num_group", num_group);
  builder_.SetParam("no_bias", no_bias ? 1 : 0);
  builder_.SetParam("fuse_relu", fuse_relu ? 1 : 0);
  builder_.SetParam("dequant_out", dequant_out ? 1 : 0);
  for (const auto &s : data) builder_.AddInput(s);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _sg_int8_elemwise_add(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    double scale_a,
    double scale_b,
    bool fuse_relu = false) {
  SymbolBuilder builder_("_sg_int8_elemwise_add");
  builder_.SetParam("scale_a", scale_a);
  builder_.SetParam("scale_b", scale_b);
  builder_.SetParam("fuse_relu", fuse_relu ? 1 : 0);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _sg_int8_fully_connected(const std::string &symbol_name,
    const std::vector<Symbol> &data,
    int num_hidden,
    double scale_out,
    bool no_bias = false,
    bool flatten = true,
    bool fuse_relu = false,
    bool dequant_out = false) {
  SymbolBuilder builder_("_sg_int8_fully_connected");
  builder_.SetParam("num_hidden", num_hidden);
  builder_.SetParam("scale_out", scale_out);
  builder_.SetParam("no_bias", no_bias ? 1 : 0);
  builder_.SetParam("flatten", flatten ? 1 : 0);
  builder_.SetParam("fuse_relu", fuse_relu ? 1 : 0);
  builder_.SetParam("dequant_out", dequant_out ? 1 : 0);
  for (const auto &s : data) builder_.AddInput(s);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _sg_int8_global_avg_pool(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("_sg_int8_global_avg_pool");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _sg_int8_pooling(const std::string &symbol_name,
    Symbol data,
    const std::string &kernel = "()",
    const std::string &pool_type = "max",
    bool global_pool = false,
    const std::string &stride = "()",
    const std::string &pad = "()",
    const std::string &pooling_convention = "valid",
    bool count_include_pad = true,
    int p_value = 2) {
  SymbolBuilder builder_("_sg_int8_pooling");
  builder_.SetParam("kernel", kernel);
  builder_.SetParam("pool_type", pool_type);
  builder_.SetParam("global_pool", global_pool ? 1 : 0);
  builder_.SetParam("stride", stride);
  builder_.SetParam("pad", pad);
  builder_.SetParam("pooling_convention", pooling_convention);
  builder_.SetParam("count_include_pad", count_include_pad ? 1 : 0);
  builder_.SetParam("p_value", p_value);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _shuffle(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("_shuffle");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _slice_assign(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    const std::string &begin,
    const std::string &end,
    const std::string &step = "()") {
  SymbolBuilder builder_("_slice_assign");
  builder_.SetParam("begin", begin);
  builder_.SetParam("end", end);
  builder_.SetParam("step", step);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _slice_assign_scalar(const std::string &symbol_name,
    Symbol data,
    const std::string &begin,
    const std::string &end,
    double scalar = 0.0,
    const std::string &step = "()") {
  SymbolBuilder builder_("_slice_assign_scalar");
  builder_.SetParam("begin", begin);
  builder_.SetParam("end", end);
  builder_.SetParam("scalar", scalar);
  builder_.SetParam("step", step);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _sparse_adagrad_update(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    Symbol data2,
    double lr,
    double epsilon = 1e-07,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0) {
  SymbolBuilder builder_("_sparse_adagrad_update");
  builder_.SetParam("lr", lr);
  builder_.SetParam("epsilon", epsilon);
  builder_.SetParam("wd", wd);
  builder_.SetParam("rescale_grad", rescale_grad);
  builder_.SetParam("clip_gradient", clip_gradient);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  builder_.SetInput("data2", data2);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _sparse_retain(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("_sparse_retain");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _square_sum(const std::string &symbol_name,
    Symbol data,
    bool keepdims = false,
    bool exclude = false) {
  SymbolBuilder builder_("_square_sum");
  builder_.SetParam("keepdims", keepdims ? 1 : 0);
  builder_.SetParam("exclude", exclude ? 1 : 0);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _sub(const std::string &symbol_name,
    Symbol lhs,
    Symbol rhs) {
  SymbolBuilder builder_("_sub");
  builder_.SetInput("lhs", lhs);
  builder_.SetInput("rhs", rhs);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _sum(const std::string &symbol_name,
    const std::vector<Symbol> &data) {
  SymbolBuilder builder_("_sum");
  for (const auto &s : data) builder_.AddInput(s);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _unravel_index(const std::string &symbol_name,
    Symbol data,
    const std::string &shape) {
  SymbolBuilder builder_("_unravel_index");
  builder_.SetParam("shape", shape);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol _zeros(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()",
    const std::string &dtype = "float32") {
  SymbolBuilder builder_("_zeros");
  builder_.SetParam("shape", shape);
  builder_.SetParam("dtype", dtype);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol abs(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("abs");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol activation(const std::string &symbol_name,
    Symbol data,
    const std::string &act_type) {
  SymbolBuilder builder_("activation");
  builder_.SetParam("act_type", act_type);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol adam_update(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    Symbol data2,
    Symbol data3,
    double lr,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    double beta1 = 0.9,
    double beta2 = 0.999,
    double epsilon = 1e-08,
    bool lazy_update = true) {
  SymbolBuilder builder_("adam_update");
  builder_.SetParam("lr", lr);
  builder_.SetParam("wd", wd);
  builder_.SetParam("rescale_grad", rescale_grad);
  builder_.SetParam("clip_gradient", clip_gradient);
  builder_.SetParam("beta1", beta1);
  builder_.SetParam("beta2", beta2);
  builder_.SetParam("epsilon", epsilon);
  builder_.SetParam("lazy_update", lazy_update ? 1 : 0);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  builder_.SetInput("data2", data2);
  builder_.SetInput("data3", data3);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol add_n(const std::string &symbol_name,
    const std::vector<Symbol> &data) {
  SymbolBuilder builder_("add_n");
  for (const auto &s : data) builder_.AddInput(s);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol amp_cast(const std::string &symbol_name,
    Symbol data,
    const std::string &dtype = "float32") {
  SymbolBuilder builder_("amp_cast");
  builder_.SetParam("dtype", dtype);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol arange(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()",
    const std::string &dtype = "float32",
    double start = 0.0,
    double step = 1.0,
    int repeat = 1,
    bool infer_range = false) {
  SymbolBuilder builder_("arange");
  builder_.SetParam("shape", shape);
  builder_.SetParam("dtype", dtype);
  builder_.SetParam("start", start);
  builder_.SetParam("step", step);
  builder_.SetParam("repeat", repeat);
  builder_.SetParam("infer_range", infer_range ? 1 : 0);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol arccos(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("arccos");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol arccosh(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("arccosh");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol arcsin(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("arcsin");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol arcsinh(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("arcsinh");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol arctan(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("arctan");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol arctanh(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("arctanh");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol argmax(const std::string &symbol_name,
    Symbol data,
    bool keepdims = false) {
  SymbolBuilder builder_("argmax");
  builder_.SetParam("keepdims", keepdims ? 1 : 0);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol argmax_channel(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("argmax_channel");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol argmin(const std::string &symbol_name,
    Symbol data,
    bool keepdims = false) {
  SymbolBuilder builder_("argmin");
  builder_.SetParam("keepdims", keepdims ? 1 : 0);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol argsort(const std::string &symbol_name,
    Symbol data,
    const std::string &axis = "(-1)",
    bool is_ascend = true,
    const std::string &dtype = "float32") {
  SymbolBuilder builder_("argsort");
  builder_.SetParam("axis", axis);
  builder_.SetParam("is_ascend", is_ascend ? 1 : 0);
  builder_.SetParam("dtype", dtype);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol batch_dot(const std::string &symbol_name,
    Symbol lhs,
    Symbol rhs,
    bool transpose_a = false,
    bool transpose_b = false) {
  SymbolBuilder builder_("batch_dot");
  builder_.SetParam("transpose_a", transpose_a ? 1 : 0);
  builder_.SetParam("transpose_b", transpose_b ? 1 : 0);
  builder_.SetInput("lhs", lhs);
  builder_.SetInput("rhs", rhs);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol batch_take(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("batch_take");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol batchnorm(const std::string &symbol_name,
    Symbol data,
    Symbol gamma,
    Symbol beta,
    Symbol moving_mean,
    Symbol moving_var,
    double eps = 0.001,
    double momentum = 0.9,
    bool fix_gamma = true,
    bool use_global_stats = false,
    bool output_mean_var = false,
    int axis = 1,
    bool cudnn_off = false) {
  SymbolBuilder builder_("batchnorm");
  builder_.SetParam("eps", eps);
  builder_.SetParam("momentum", momentum);
  builder_.SetParam("fix_gamma", fix_gamma ? 1 : 0);
  builder_.SetParam("use_global_stats", use_global_stats ? 1 : 0);
  builder_.SetParam("output_mean_var", output_mean_var ? 1 : 0);
  builder_.SetParam("axis", axis);
  builder_.SetParam("cudnn_off", cudnn_off ? 1 : 0);
  builder_.SetInput("data", data);
  builder_.SetInput("gamma", gamma);
  builder_.SetInput("beta", beta);
  builder_.SetInput("moving_mean", moving_mean);
  builder_.SetInput("moving_var", moving_var);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol bilinearsampler(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("bilinearsampler");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol bipartite_matching(const std::string &symbol_name,
    Symbol data,
    double threshold,
    bool is_ascend = false,
    int topk = -1) {
  SymbolBuilder builder_("bipartite_matching");
  builder_.SetParam("threshold", threshold);
  builder_.SetParam("is_ascend", is_ascend ? 1 : 0);
  builder_.SetParam("topk", topk);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol block_diffusion_ce(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    Symbol data2,
    int block,
    int mask_id) {
  SymbolBuilder builder_("block_diffusion_ce");
  builder_.SetParam("block", block);
  builder_.SetParam("mask_id", mask_id);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  builder_.SetInput("data2", data2);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol box_iou(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    const std::string &format = "corner") {
  SymbolBuilder builder_("box_iou");
  builder_.SetParam("format", format);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol box_nms(const std::string &symbol_name,
    Symbol data,
    double overlap_thresh = 0.5,
    double valid_thresh = 0.0,
    int topk = -1,
    int coord_start = 2,
    int score_index = 1,
    int id_index = -1,
    bool force_suppress = false,
    const std::string &in_format = "corner",
    const std::string &out_format = "corner") {
  SymbolBuilder builder_("box_nms");
  builder_.SetParam("overlap_thresh", overlap_thresh);
  builder_.SetParam("valid_thresh", valid_thresh);
  builder_.SetParam("topk", topk);
  builder_.SetParam("coord_start", coord_start);
  builder_.SetParam("score_index", score_index);
  builder_.SetParam("id_index", id_index);
  builder_.SetParam("force_suppress", force_suppress ? 1 : 0);
  builder_.SetParam("in_format", in_format);
  builder_.SetParam("out_format", out_format);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol broadcast_add(const std::string &symbol_name,
    Symbol lhs,
    Symbol rhs) {
  SymbolBuilder builder_("broadcast_add");
  builder_.SetInput("lhs", lhs);
  builder_.SetInput("rhs", rhs);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol broadcast_axes(const std::string &symbol_name,
    Symbol data,
    const std::string &axis = "()",
    const std::string &size = "()") {
  SymbolBuilder builder_("broadcast_axes");
  builder_.SetParam("axis", axis);
  builder_.SetParam("size", size);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol broadcast_axis(const std::string &symbol_name,
    Symbol data,
    const std::string &axis = "()",
    const std::string &size = "()") {
  SymbolBuilder builder_("broadcast_axis");
  builder_.SetParam("axis", axis);
  builder_.SetParam("size", size);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol broadcast_div(const std::string &symbol_name,
    Symbol lhs,
    Symbol rhs) {
  SymbolBuilder builder_("broadcast_div");
  builder_.SetInput("lhs", lhs);
  builder_.SetInput("rhs", rhs);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol broadcast_equal(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("broadcast_equal");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol broadcast_greater(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("broadcast_greater");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol broadcast_greater_equal(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("broadcast_greater_equal");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol broadcast_hypot(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("broadcast_hypot");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol broadcast_lesser(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("broadcast_lesser");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol broadcast_lesser_equal(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("broadcast_lesser_equal");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol broadcast_like(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("broadcast_like");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol broadcast_logical_and(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("broadcast_logical_and");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol broadcast_logical_or(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("broadcast_logical_or");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol broadcast_logical_xor(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("broadcast_logical_xor");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol broadcast_maximum(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("broadcast_maximum");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol broadcast_minimum(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("broadcast_minimum");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol broadcast_mod(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("broadcast_mod");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol broadcast_mul(const std::string &symbol_name,
    Symbol lhs,
    Symbol rhs) {
  SymbolBuilder builder_("broadcast_mul");
  builder_.SetInput("lhs", lhs);
  builder_.SetInput("rhs", rhs);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol broadcast_not_equal(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("broadcast_not_equal");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol broadcast_power(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("broadcast_power");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol broadcast_sub(const std::string &symbol_name,
    Symbol lhs,
    Symbol rhs) {
  SymbolBuilder builder_("broadcast_sub");
  builder_.SetInput("lhs", lhs);
  builder_.SetInput("rhs", rhs);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol broadcast_to(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()") {
  SymbolBuilder builder_("broadcast_to");
  builder_.SetParam("shape", shape);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol cast(const std::string &symbol_name,
    Symbol data,
    const std::string &dtype) {
  SymbolBuilder builder_("cast");
  builder_.SetParam("dtype", dtype);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol cast_storage(const std::string &symbol_name,
    Symbol data,
    const std::string &stype = "default") {
  SymbolBuilder builder_("cast_storage");
  builder_.SetParam("stype", stype);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol cbrt(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("cbrt");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol ceil(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("ceil");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol clip(const std::string &symbol_name,
    Symbol data,
    double a_min,
    double a_max) {
  SymbolBuilder builder_("clip");
  builder_.SetParam("a_min", a_min);
  builder_.SetParam("a_max", a_max);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol concat(const std::string &symbol_name,
    const std::vector<Symbol> &data,
    int dim = 1) {
  SymbolBuilder builder_("concat");
  builder_.SetParam("dim", dim);
  builder_.SetParam("num_args", data.size());
  for (const auto &s : data) builder_.AddInput(s);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol convolution(const std::string &symbol_name,
    Symbol data,
    Symbol weight,
    Symbol bias,
    const std::string &kernel,
    int num_filter,
    const std::string &stride = "()",
    const std::string &dilate = "()",
    const std::string &pad = "()",
    int num_group = 1,
    bool no_bias = false,
    int workspace = 1024,
    bool cudnn_off = false) {
  SymbolBuilder builder_("convolution");
  builder_.SetParam("kernel", kernel);
  builder_.SetParam("num_filter", num_filter);
  builder_.SetParam("stride", stride);
  builder_.SetParam("dilate", dilate);
  builder_.SetParam("pad", pad);
  builder_.SetParam("num_group", num_group);
  builder_.SetParam("no_bias", no_bias ? 1 : 0);
  builder_.SetParam("workspace", workspace);
  builder_.SetParam("cudnn_off", cudnn_off ? 1 : 0);
  builder_.SetInput("data", data);
  builder_.SetInput("weight", weight);
  builder_.SetInput("bias", bias);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol correlation(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    int kernel_size = 1,
    int max_displacement = 1,
    int stride1 = 1,
    int stride2 = 1,
    int pad_size = 0,
    bool is_multiply = true) {
  SymbolBuilder builder_("correlation");
  builder_.SetParam("kernel_size", kernel_size);
  builder_.SetParam("max_displacement", max_displacement);
  builder_.SetParam("stride1", stride1);
  builder_.SetParam("stride2", stride2);
  builder_.SetParam("pad_size", pad_size);
  builder_.SetParam("is_multiply", is_multiply ? 1 : 0);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol cos(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("cos");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol cosh(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("cosh");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol count_sketch(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    Symbol data2,
    int out_dim,
    int processing_batch_size = 32) {
  SymbolBuilder builder_("count_sketch");
  builder_.SetParam("out_dim", out_dim);
  builder_.SetParam("processing_batch_size", processing_batch_size);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  builder_.SetInput("data2", data2);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol crop(const std::string &symbol_name,
    Symbol data,
    const std::string &begin = "()",
    const std::string &end = "()",
    const std::string &step = "()") {
  SymbolBuilder builder_("crop");
  builder_.SetParam("begin", begin);
  builder_.SetParam("end", end);
  builder_.SetParam("step", step);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol crop_like(const std::string &symbol_name,
    const std::vector<Symbol> &data,
    const std::string &offset = "(0, 0)",
    const std::string &h_w = "(0, 0)",
    bool center_crop = false) {
  SymbolBuilder builder_("crop_like");
  builder_.SetParam("offset", offset);
  builder_.SetParam("h_w", h_w);
  builder_.SetParam("center_crop", center_crop ? 1 : 0);
  builder_.SetParam("num_args", data.size());
  for (const auto &s : data) builder_.AddInput(s);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol ctc_loss(const std::string &symbol_name,
    const std::vector<Symbol> &data,
    bool use_data_lengths = false,
    bool use_label_lengths = false,
    const std::string &blank_label = "first") {
  SymbolBuilder builder_("ctc_loss");
  builder_.SetParam("use_data_lengths", use_data_lengths ? 1 : 0);
  builder_.SetParam("use_label_lengths", use_label_lengths ? 1 : 0);
  builder_.SetParam("blank_label", blank_label);
  for (const auto &s : data) builder_.AddInput(s);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol deconvolution(const std::string &symbol_name,
    Symbol data,
    Symbol weight,
    Symbol bias,
    const std::string &kernel,
    int num_filter,
    const std::string &stride = "()",
    const std::string &dilate = "()",
    const std::string &pad = "()",
    int num_group = 1,
    bool no_bias = false,
    int workspace = 1024,
    bool cudnn_off = false,
    const std::string &adj = "()",
    const std::string &target_shape = "()") {
  SymbolBuilder builder_("deconvolution");
  builder_.SetParam("kernel", kernel);
  builder_.SetParam("num_filter", num_filter);
  builder_.SetParam("stride", stride);
  builder_.SetParam("dilate", dilate);
  builder_.SetParam("pad", pad);
  builder_.SetParam("num_group", num_group);
  builder_.SetParam("no_bias", no_bias ? 1 : 0);
  builder_.SetParam("workspace", workspace);
  builder_.SetParam("cudnn_off", cudnn_off ? 1 : 0);
  builder_.SetParam("adj", adj);
  builder_.SetParam("target_shape", target_shape);
  builder_.SetInput("data", data);
  builder_.SetInput("weight", weight);
  builder_.SetInput("bias", bias);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol degrees(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("degrees");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol deltaattention(const std::string &symbol_name,
    Symbol data,
    Symbol query_weight,
    Symbol key_weight,
    Symbol value_weight,
    Symbol q_conv_weight,
    Symbol k_conv_weight,
    Symbol v_conv_weight,
    Symbol f_a_weight,
    Symbol f_b_weight,
    Symbol A_log,
    Symbol dt_bias,
    Symbol beta_weight,
    Symbol g_a_weight,
    Symbol g_b_weight,
    Symbol o_norm_gamma,
    Symbol out_proj_weight,
    int num_heads,
    int head_dim,
    int conv_kernel = 4,
    double eps = 1e-05) {
  SymbolBuilder builder_("deltaattention");
  builder_.SetParam("num_heads", num_heads);
  builder_.SetParam("head_dim", head_dim);
  builder_.SetParam("conv_kernel", conv_kernel);
  builder_.SetParam("eps", eps);
  builder_.SetInput("data", data);
  builder_.SetInput("query_weight", query_weight);
  builder_.SetInput("key_weight", key_weight);
  builder_.SetInput("value_weight", value_weight);
  builder_.SetInput("q_conv_weight", q_conv_weight);
  builder_.SetInput("k_conv_weight", k_conv_weight);
  builder_.SetInput("v_conv_weight", v_conv_weight);
  builder_.SetInput("f_a_weight", f_a_weight);
  builder_.SetInput("f_b_weight", f_b_weight);
  builder_.SetInput("A_log", A_log);
  builder_.SetInput("dt_bias", dt_bias);
  builder_.SetInput("beta_weight", beta_weight);
  builder_.SetInput("g_a_weight", g_a_weight);
  builder_.SetInput("g_b_weight", g_b_weight);
  builder_.SetInput("o_norm_gamma", o_norm_gamma);
  builder_.SetInput("out_proj_weight", out_proj_weight);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol depth_to_space(const std::string &symbol_name,
    Symbol data,
    int block_size) {
  SymbolBuilder builder_("depth_to_space");
  builder_.SetParam("block_size", block_size);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol dequantize(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    Symbol data2,
    const std::string &out_type = "float32") {
  SymbolBuilder builder_("dequantize");
  builder_.SetParam("out_type", out_type);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  builder_.SetInput("data2", data2);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol diag(const std::string &symbol_name,
    Symbol data,
    int k = 0) {
  SymbolBuilder builder_("diag");
  builder_.SetParam("k", k);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol div_sqrt_dim(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("div_sqrt_dim");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol dot(const std::string &symbol_name,
    Symbol lhs,
    Symbol rhs,
    bool transpose_a = false,
    bool transpose_b = false) {
  SymbolBuilder builder_("dot");
  builder_.SetParam("transpose_a", transpose_a ? 1 : 0);
  builder_.SetParam("transpose_b", transpose_b ? 1 : 0);
  builder_.SetInput("lhs", lhs);
  builder_.SetInput("rhs", rhs);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol dropout(const std::string &symbol_name,
    Symbol data,
    double p = 0.5,
    const std::string &mode = "training",
    const std::string &axes = "()",
    bool cudnn_off = false) {
  SymbolBuilder builder_("dropout");
  builder_.SetParam("p", p);
  builder_.SetParam("mode", mode);
  builder_.SetParam("axes", axes);
  builder_.SetParam("cudnn_off", cudnn_off ? 1 : 0);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol elemwise_add(const std::string &symbol_name,
    Symbol lhs,
    Symbol rhs) {
  SymbolBuilder builder_("elemwise_add");
  builder_.SetInput("lhs", lhs);
  builder_.SetInput("rhs", rhs);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol elemwise_div(const std::string &symbol_name,
    Symbol lhs,
    Symbol rhs) {
  SymbolBuilder builder_("elemwise_div");
  builder_.SetInput("lhs", lhs);
  builder_.SetInput("rhs", rhs);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol elemwise_mul(const std::string &symbol_name,
    Symbol lhs,
    Symbol rhs) {
  SymbolBuilder builder_("elemwise_mul");
  builder_.SetInput("lhs", lhs);
  builder_.SetInput("rhs", rhs);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol elemwise_sub(const std::string &symbol_name,
    Symbol lhs,
    Symbol rhs) {
  SymbolBuilder builder_("elemwise_sub");
  builder_.SetInput("lhs", lhs);
  builder_.SetInput("rhs", rhs);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol embedding(const std::string &symbol_name,
    Symbol data,
    Symbol weight,
    int input_dim,
    int output_dim,
    const std::string &dtype = "float32",
    bool sparse_grad = false) {
  SymbolBuilder builder_("embedding");
  builder_.SetParam("input_dim", input_dim);
  builder_.SetParam("output_dim", output_dim);
  builder_.SetParam("dtype", dtype);
  builder_.SetParam("sparse_grad", sparse_grad ? 1 : 0);
  builder_.SetInput("data", data);
  builder_.SetInput("weight", weight);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol erf(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("erf");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol erfinv(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("erfinv");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol exp(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("exp");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol expand_dims(const std::string &symbol_name,
    Symbol data,
    int axis) {
  SymbolBuilder builder_("expand_dims");
  builder_.SetParam("axis", axis);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol expm1(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("expm1");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol eye(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()",
    const std::string &dtype = "float32",
    int N = 0,
    int M = 0,
    int k = 0) {
  SymbolBuilder builder_("eye");
  builder_.SetParam("shape", shape);
  builder_.SetParam("dtype", dtype);
  builder_.SetParam("N", N);
  builder_.SetParam("M", M);
  builder_.SetParam("k", k);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol fft(const std::string &symbol_name,
    Symbol data,
    int compute_size = 128) {
  SymbolBuilder builder_("fft");
  builder_.SetParam("compute_size", compute_size);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol fix(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("fix");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol flatten(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("flatten");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol flip(const std::string &symbol_name,
    Symbol data,
    const std::string &axis) {
  SymbolBuilder builder_("flip");
  builder_.SetParam("axis", axis);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol floor(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("floor");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol ftml_update(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    Symbol data2,
    Symbol data3,
    Symbol data4,
    double lr,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    double beta1 = 0.6,
    double beta2 = 0.999,
    double epsilon = 1e-08,
    int t = 1) {
  SymbolBuilder builder_("ftml_update");
  builder_.SetParam("lr", lr);
  builder_.SetParam("wd", wd);
  builder_.SetParam("rescale_grad", rescale_grad);
  builder_.SetParam("clip_gradient", clip_gradient);
  builder_.SetParam("beta1", beta1);
  builder_.SetParam("beta2", beta2);
  builder_.SetParam("epsilon", epsilon);
  builder_.SetParam("t", t);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  builder_.SetInput("data2", data2);
  builder_.SetInput("data3", data3);
  builder_.SetInput("data4", data4);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol ftrl_update(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    Symbol data2,
    Symbol data3,
    double lr,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    double lamda1 = 0.01,
    double beta = 1.0) {
  SymbolBuilder builder_("ftrl_update");
  builder_.SetParam("lr", lr);
  builder_.SetParam("wd", wd);
  builder_.SetParam("rescale_grad", rescale_grad);
  builder_.SetParam("clip_gradient", clip_gradient);
  builder_.SetParam("lamda1", lamda1);
  builder_.SetParam("beta", beta);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  builder_.SetInput("data2", data2);
  builder_.SetInput("data3", data3);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol full(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()",
    const std::string &dtype = "float32",
    double value = 0.0) {
  SymbolBuilder builder_("full");
  builder_.SetParam("shape", shape);
  builder_.SetParam("dtype", dtype);
  builder_.SetParam("value", value);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol fullyconnected(const std::string &symbol_name,
    Symbol data,
    Symbol weight,
    Symbol bias,
    int num_hidden,
    bool no_bias = false,
    bool flatten = true) {
  SymbolBuilder builder_("fullyconnected");
  builder_.SetParam("num_hidden", num_hidden);
  builder_.SetParam("no_bias", no_bias ? 1 : 0);
  builder_.SetParam("flatten", flatten ? 1 : 0);
  builder_.SetInput("data", data);
  builder_.SetInput("weight", weight);
  builder_.SetInput("bias", bias);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol gamma(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("gamma");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol gammaln(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("gammaln");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol gather_nd(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("gather_nd");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol gridgenerator(const std::string &symbol_name,
    Symbol data,
    const std::string &transform_type,
    const std::string &target_shape = "(0, 0)") {
  SymbolBuilder builder_("gridgenerator");
  builder_.SetParam("transform_type", transform_type);
  builder_.SetParam("target_shape", target_shape);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol hard_sigmoid(const std::string &symbol_name,
    Symbol data,
    double alpha = 0.2,
    double beta = 0.5) {
  SymbolBuilder builder_("hard_sigmoid");
  builder_.SetParam("alpha", alpha);
  builder_.SetParam("beta", beta);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol identity(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("identity");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol ifft(const std::string &symbol_name,
    Symbol data,
    int compute_size = 128) {
  SymbolBuilder builder_("ifft");
  builder_.SetParam("compute_size", compute_size);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol instancenorm(const std::string &symbol_name,
    Symbol data,
    Symbol gamma,
    Symbol beta,
    double eps = 0.001) {
  SymbolBuilder builder_("instancenorm");
  builder_.SetParam("eps", eps);
  builder_.SetInput("data", data);
  builder_.SetInput("gamma", gamma);
  builder_.SetInput("beta", beta);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol khatri_rao(const std::string &symbol_name,
    const std::vector<Symbol> &data) {
  SymbolBuilder builder_("khatri_rao");
  for (const auto &s : data) builder_.AddInput(s);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol layernorm(const std::string &symbol_name,
    Symbol data,
    Symbol gamma,
    Symbol beta,
    int axis = -1,
    double eps = 1e-05,
    bool output_mean_var = false) {
  SymbolBuilder builder_("layernorm");
  builder_.SetParam("axis", axis);
  builder_.SetParam("eps", eps);
  builder_.SetParam("output_mean_var", output_mean_var ? 1 : 0);
  builder_.SetInput("data", data);
  builder_.SetInput("gamma", gamma);
  builder_.SetInput("beta", beta);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol leakyrelu(const std::string &symbol_name,
    Symbol data,
    Symbol gamma,
    const std::string &act_type = "leaky",
    double slope = 0.25,
    double lower_bound = 0.125,
    double upper_bound = 0.334) {
  SymbolBuilder builder_("leakyrelu");
  builder_.SetParam("act_type", act_type);
  builder_.SetParam("slope", slope);
  builder_.SetParam("lower_bound", lower_bound);
  builder_.SetParam("upper_bound", upper_bound);
  builder_.SetInput("data", data);
  builder_.SetInput("gamma", gamma);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol linalg_gelqf(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("linalg_gelqf");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol linalg_gemm(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    Symbol data2,
    bool transpose_a = false,
    bool transpose_b = false,
    double alpha = 1.0,
    double beta = 1.0,
    int axis = -3) {
  SymbolBuilder builder_("linalg_gemm");
  builder_.SetParam("transpose_a", transpose_a ? 1 : 0);
  builder_.SetParam("transpose_b", transpose_b ? 1 : 0);
  builder_.SetParam("alpha", alpha);
  builder_.SetParam("beta", beta);
  builder_.SetParam("axis", axis);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  builder_.SetInput("data2", data2);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol linalg_gemm2(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    bool transpose_a = false,
    bool transpose_b = false,
    double alpha = 1.0,
    int axis = -3) {
  SymbolBuilder builder_("linalg_gemm2");
  builder_.SetParam("transpose_a", transpose_a ? 1 : 0);
  builder_.SetParam("transpose_b", transpose_b ? 1 : 0);
  builder_.SetParam("alpha", alpha);
  builder_.SetParam("axis", axis);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol linalg_potrf(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("linalg_potrf");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol linalg_potri(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("linalg_potri");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol linalg_sumlogdiag(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("linalg_sumlogdiag");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol linalg_syevd(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("linalg_syevd");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol linalg_syrk(const std::string &symbol_name,
    Symbol data,
    bool transpose = false,
    double alpha = 1.0) {
  SymbolBuilder builder_("linalg_syrk");
  builder_.SetParam("transpose", transpose ? 1 : 0);
  builder_.SetParam("alpha", alpha);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol linalg_trmm(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    bool transpose = false,
    bool rightside = false,
    double alpha = 1.0) {
  SymbolBuilder builder_("linalg_trmm");
  builder_.SetParam("transpose", transpose ? 1 : 0);
  builder_.SetParam("rightside", rightside ? 1 : 0);
  builder_.SetParam("alpha", alpha);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol linalg_trsm(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    bool transpose = false,
    bool rightside = false,
    double alpha = 1.0) {
  SymbolBuilder builder_("linalg_trsm");
  builder_.SetParam("transpose", transpose ? 1 : 0);
  builder_.SetParam("rightside", rightside ? 1 : 0);
  builder_.SetParam("alpha", alpha);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol linearregressionoutput(const std::string &symbol_name,
    Symbol data,
    Symbol label,
    double grad_scale = 1.0) {
  SymbolBuilder builder_("linearregressionoutput");
  builder_.SetParam("grad_scale", grad_scale);
  builder_.SetInput("data", data);
  builder_.SetInput("label", label);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol linspace(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()",
    const std::string &dtype = "float32",
    double start = 0.0,
    double stop = 1.0,
    int num = 50,
    bool endpoint = true) {
  SymbolBuilder builder_("linspace");
  builder_.SetParam("shape", shape);
  builder_.SetParam("dtype", dtype);
  builder_.SetParam("start", start);
  builder_.SetParam("stop", stop);
  builder_.SetParam("num", num);
  builder_.SetParam("endpoint", endpoint ? 1 : 0);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol log(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("log");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol log10(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("log10");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol log1p(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("log1p");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol log2(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("log2");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol log_softmax(const std::string &symbol_name,
    Symbol data,
    int axis = -1) {
  SymbolBuilder builder_("log_softmax");
  builder_.SetParam("axis", axis);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol logical_not(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("logical_not");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol logisticregressionoutput(const std::string &symbol_name,
    Symbol data,
    Symbol label,
    double grad_scale = 1.0) {
  SymbolBuilder builder_("logisticregressionoutput");
  builder_.SetParam("grad_scale", grad_scale);
  builder_.SetInput("data", data);
  builder_.SetInput("label", label);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol lrn(const std::string &symbol_name,
    Symbol data,
    int nsize,
    double alpha = 0.0001,
    double beta = 0.75,
    double knorm = 2.0) {
  SymbolBuilder builder_("lrn");
  builder_.SetParam("nsize", nsize);
  builder_.SetParam("alpha", alpha);
  builder_.SetParam("beta", beta);
  builder_.SetParam("knorm", knorm);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol maeregressionoutput(const std::string &symbol_name,
    Symbol data,
    Symbol label,
    double grad_scale = 1.0) {
  SymbolBuilder builder_("maeregressionoutput");
  builder_.SetParam("grad_scale", grad_scale);
  builder_.SetInput("data", data);
  builder_.SetInput("label", label);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol make_loss(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("make_loss");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol max(const std::string &symbol_name,
    Symbol data,
    bool keepdims = false,
    bool exclude = false) {
  SymbolBuilder builder_("max");
  builder_.SetParam("keepdims", keepdims ? 1 : 0);
  builder_.SetParam("exclude", exclude ? 1 : 0);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol max_axis(const std::string &symbol_name,
    Symbol data,
    bool keepdims = false,
    bool exclude = false) {
  SymbolBuilder builder_("max_axis");
  builder_.SetParam("keepdims", keepdims ? 1 : 0);
  builder_.SetParam("exclude", exclude ? 1 : 0);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol mean(const std::string &symbol_name,
    Symbol data,
    bool keepdims = false,
    bool exclude = false) {
  SymbolBuilder builder_("mean");
  builder_.SetParam("keepdims", keepdims ? 1 : 0);
  builder_.SetParam("exclude", exclude ? 1 : 0);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol min(const std::string &symbol_name,
    Symbol data,
    bool keepdims = false,
    bool exclude = false) {
  SymbolBuilder builder_("min");
  builder_.SetParam("keepdims", keepdims ? 1 : 0);
  builder_.SetParam("exclude", exclude ? 1 : 0);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol min_axis(const std::string &symbol_name,
    Symbol data,
    bool keepdims = false,
    bool exclude = false) {
  SymbolBuilder builder_("min_axis");
  builder_.SetParam("keepdims", keepdims ? 1 : 0);
  builder_.SetParam("exclude", exclude ? 1 : 0);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol mp_sgd_mom_update(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    Symbol data2,
    Symbol data3,
    double lr,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    double momentum = 0.0) {
  SymbolBuilder builder_("mp_sgd_mom_update");
  builder_.SetParam("lr", lr);
  builder_.SetParam("wd", wd);
  builder_.SetParam("rescale_grad", rescale_grad);
  builder_.SetParam("clip_gradient", clip_gradient);
  builder_.SetParam("momentum", momentum);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  builder_.SetInput("data2", data2);
  builder_.SetInput("data3", data3);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol mp_sgd_update(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    Symbol data2,
    double lr,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0) {
  SymbolBuilder builder_("mp_sgd_update");
  builder_.SetParam("lr", lr);
  builder_.SetParam("wd", wd);
  builder_.SetParam("rescale_grad", rescale_grad);
  builder_.SetParam("clip_gradient", clip_gradient);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  builder_.SetInput("data2", data2);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol multiheadattention(const std::string &symbol_name,
    Symbol data,
    Symbol query_weight,
    Symbol key_weight,
    Symbol value_weight,
    Symbol out_proj_weight,
    Symbol q_norm_gamma,
    Symbol k_norm_gamma,
    Symbol q_a_weight,
    Symbol q_a_norm_gamma,
    Symbol q_b_weight,
    Symbol kv_a_weight,
    Symbol kv_a_norm_gamma,
    Symbol kv_b_weight,
    int num_heads,
    bool causal = true,
    int num_kv_heads = 0,
    bool qk_norm = false,
    double rope_theta = 0.0,
    double eps = 1e-05,
    int head_dim = 0,
    int window = 0,
    const std::string &rope_yarn = "()",
    int q_lora_rank = 0,
    int kv_lora_rank = 0,
    int qk_rope_head_dim = 0,
    int v_head_dim = 0,
    int diffusion_block = 0) {
  SymbolBuilder builder_("multiheadattention");
  builder_.SetParam("num_heads", num_heads);
  builder_.SetParam("causal", causal ? 1 : 0);
  builder_.SetParam("num_kv_heads", num_kv_heads);
  builder_.SetParam("qk_norm", qk_norm ? 1 : 0);
  builder_.SetParam("rope_theta", rope_theta);
  builder_.SetParam("eps", eps);
  builder_.SetParam("head_dim", head_dim);
  builder_.SetParam("window", window);
  builder_.SetParam("rope_yarn", rope_yarn);
  builder_.SetParam("q_lora_rank", q_lora_rank);
  builder_.SetParam("kv_lora_rank", kv_lora_rank);
  builder_.SetParam("qk_rope_head_dim", qk_rope_head_dim);
  builder_.SetParam("v_head_dim", v_head_dim);
  builder_.SetParam("diffusion_block", diffusion_block);
  builder_.SetInput("data", data);
  builder_.SetInput("query_weight", query_weight);
  builder_.SetInput("key_weight", key_weight);
  builder_.SetInput("value_weight", value_weight);
  builder_.SetInput("out_proj_weight", out_proj_weight);
  builder_.SetInput("q_norm_gamma", q_norm_gamma);
  builder_.SetInput("k_norm_gamma", k_norm_gamma);
  builder_.SetInput("q_a_weight", q_a_weight);
  builder_.SetInput("q_a_norm_gamma", q_a_norm_gamma);
  builder_.SetInput("q_b_weight", q_b_weight);
  builder_.SetInput("kv_a_weight", kv_a_weight);
  builder_.SetInput("kv_a_norm_gamma", kv_a_norm_gamma);
  builder_.SetInput("kv_b_weight", kv_b_weight);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol nag_mom_update(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    Symbol data2,
    double lr,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    double momentum = 0.0) {
  SymbolBuilder builder_("nag_mom_update");
  builder_.SetParam("lr", lr);
  builder_.SetParam("wd", wd);
  builder_.SetParam("rescale_grad", rescale_grad);
  builder_.SetParam("clip_gradient", clip_gradient);
  builder_.SetParam("momentum", momentum);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  builder_.SetInput("data2", data2);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol nanprod(const std::string &symbol_name,
    Symbol data,
    bool keepdims = false,
    bool exclude = false) {
  SymbolBuilder builder_("nanprod");
  builder_.SetParam("keepdims", keepdims ? 1 : 0);
  builder_.SetParam("exclude", exclude ? 1 : 0);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol nansum(const std::string &symbol_name,
    Symbol data,
    bool keepdims = false,
    bool exclude = false) {
  SymbolBuilder builder_("nansum");
  builder_.SetParam("keepdims", keepdims ? 1 : 0);
  builder_.SetParam("exclude", exclude ? 1 : 0);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol negative(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("negative");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol newreorg(const std::string &symbol_name,
    Symbol data,
    int stride = 2) {
  SymbolBuilder builder_("newreorg");
  builder_.SetParam("stride", stride);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol norm(const std::string &symbol_name,
    Symbol data,
    int ord = 2,
    bool keepdims = false) {
  SymbolBuilder builder_("norm");
  builder_.SetParam("ord", ord);
  builder_.SetParam("keepdims", keepdims ? 1 : 0);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol normal(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()",
    double loc = 0.0,
    double scale = 1.0) {
  SymbolBuilder builder_("normal");
  builder_.SetParam("shape", shape);
  builder_.SetParam("loc", loc);
  builder_.SetParam("scale", scale);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol normalize(const std::string &symbol_name,
    Symbol data,
    const std::string &mean = "(0)",
    const std::string &std = "(1)") {
  SymbolBuilder builder_("normalize");
  builder_.SetParam("mean", mean);
  builder_.SetParam("std", std);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol one_hot(const std::string &symbol_name,
    Symbol data,
    int depth,
    double on_value = 1.0,
    double off_value = 0.0,
    const std::string &dtype = "float32") {
  SymbolBuilder builder_("one_hot");
  builder_.SetParam("depth", depth);
  builder_.SetParam("on_value", on_value);
  builder_.SetParam("off_value", off_value);
  builder_.SetParam("dtype", dtype);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol ones(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()",
    const std::string &dtype = "float32") {
  SymbolBuilder builder_("ones");
  builder_.SetParam("shape", shape);
  builder_.SetParam("dtype", dtype);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol ones_like(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("ones_like");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol pad(const std::string &symbol_name,
    Symbol data,
    const std::string &pad_width,
    const std::string &mode = "constant",
    double constant_value = 0.0) {
  SymbolBuilder builder_("pad");
  builder_.SetParam("pad_width", pad_width);
  builder_.SetParam("mode", mode);
  builder_.SetParam("constant_value", constant_value);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol pick(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    const std::string &axis = "(-1)",
    bool keepdims = false,
    const std::string &mode = "clip") {
  SymbolBuilder builder_("pick");
  builder_.SetParam("axis", axis);
  builder_.SetParam("keepdims", keepdims ? 1 : 0);
  builder_.SetParam("mode", mode);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol pooling(const std::string &symbol_name,
    Symbol data,
    const std::string &kernel = "()",
    const std::string &pool_type = "max",
    bool global_pool = false,
    bool cudnn_off = false,
    const std::string &pooling_convention = "valid",
    const std::string &stride = "()",
    const std::string &pad = "()",
    int p_value = 2,
    bool count_include_pad = true) {
  SymbolBuilder builder_("pooling");
  builder_.SetParam("kernel", kernel);
  builder_.SetParam("pool_type", pool_type);
  builder_.SetParam("global_pool", global_pool ? 1 : 0);
  builder_.SetParam("cudnn_off", cudnn_off ? 1 : 0);
  builder_.SetParam("pooling_convention", pooling_convention);
  builder_.SetParam("stride", stride);
  builder_.SetParam("pad", pad);
  builder_.SetParam("p_value", p_value);
  builder_.SetParam("count_include_pad", count_include_pad ? 1 : 0);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol prod(const std::string &symbol_name,
    Symbol data,
    bool keepdims = false,
    bool exclude = false) {
  SymbolBuilder builder_("prod");
  builder_.SetParam("keepdims", keepdims ? 1 : 0);
  builder_.SetParam("exclude", exclude ? 1 : 0);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol quadratic(const std::string &symbol_name,
    Symbol data,
    double a = 0.0,
    double b = 0.0,
    double c = 0.0) {
  SymbolBuilder builder_("quadratic");
  builder_.SetParam("a", a);
  builder_.SetParam("b", b);
  builder_.SetParam("c", c);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol quantize(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    Symbol data2,
    const std::string &out_type = "int8") {
  SymbolBuilder builder_("quantize");
  builder_.SetParam("out_type", out_type);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  builder_.SetInput("data2", data2);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol quantize_v2(const std::string &symbol_name,
    Symbol data,
    const std::string &out_type = "int8") {
  SymbolBuilder builder_("quantize_v2");
  builder_.SetParam("out_type", out_type);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol radians(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("radians");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol random_exponential(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()",
    double lam = 1.0) {
  SymbolBuilder builder_("random_exponential");
  builder_.SetParam("shape", shape);
  builder_.SetParam("lam", lam);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol random_gamma(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()",
    double alpha = 1.0,
    double beta = 1.0) {
  SymbolBuilder builder_("random_gamma");
  builder_.SetParam("shape", shape);
  builder_.SetParam("alpha", alpha);
  builder_.SetParam("beta", beta);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol random_generalized_negative_binomial(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()",
    double mu = 1.0,
    double alpha = 1.0) {
  SymbolBuilder builder_("random_generalized_negative_binomial");
  builder_.SetParam("shape", shape);
  builder_.SetParam("mu", mu);
  builder_.SetParam("alpha", alpha);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol random_negative_binomial(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()",
    int k = 1,
    double p = 1.0) {
  SymbolBuilder builder_("random_negative_binomial");
  builder_.SetParam("shape", shape);
  builder_.SetParam("k", k);
  builder_.SetParam("p", p);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol random_normal(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()",
    double loc = 0.0,
    double scale = 1.0) {
  SymbolBuilder builder_("random_normal");
  builder_.SetParam("shape", shape);
  builder_.SetParam("loc", loc);
  builder_.SetParam("scale", scale);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol random_poisson(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()",
    double lam = 1.0) {
  SymbolBuilder builder_("random_poisson");
  builder_.SetParam("shape", shape);
  builder_.SetParam("lam", lam);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol random_randint(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()",
    int low = 0,
    int high = 1) {
  SymbolBuilder builder_("random_randint");
  builder_.SetParam("shape", shape);
  builder_.SetParam("low", low);
  builder_.SetParam("high", high);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol random_uniform(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()",
    double low = 0.0,
    double high = 1.0) {
  SymbolBuilder builder_("random_uniform");
  builder_.SetParam("shape", shape);
  builder_.SetParam("low", low);
  builder_.SetParam("high", high);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol ravel_multi_index(const std::string &symbol_name,
    Symbol data,
    const std::string &shape) {
  SymbolBuilder builder_("ravel_multi_index");
  builder_.SetParam("shape", shape);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol rcbrt(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("rcbrt");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol reciprocal(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("reciprocal");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol relu(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("relu");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol reorg(const std::string &symbol_name,
    Symbol data,
    int stride = 2) {
  SymbolBuilder builder_("reorg");
  builder_.SetParam("stride", stride);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol repeat(const std::string &symbol_name,
    Symbol data,
    int repeats) {
  SymbolBuilder builder_("repeat");
  builder_.SetParam("repeats", repeats);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol requantize(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    Symbol data2) {
  SymbolBuilder builder_("requantize");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  builder_.SetInput("data2", data2);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol reshape(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()",
    bool reverse = false,
    const std::string &target_shape = "()",
    bool keep_highest = false) {
  SymbolBuilder builder_("reshape");
  builder_.SetParam("shape", shape);
  builder_.SetParam("reverse", reverse ? 1 : 0);
  builder_.SetParam("target_shape", target_shape);
  builder_.SetParam("keep_highest", keep_highest ? 1 : 0);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol reshape_like(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("reshape_like");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol reverse(const std::string &symbol_name,
    Symbol data,
    const std::string &axis) {
  SymbolBuilder builder_("reverse");
  builder_.SetParam("axis", axis);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol rint(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("rint");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol rmsnorm(const std::string &symbol_name,
    Symbol data,
    Symbol gamma,
    double eps = 1e-05) {
  SymbolBuilder builder_("rmsnorm");
  builder_.SetParam("eps", eps);
  builder_.SetInput("data", data);
  builder_.SetInput("gamma", gamma);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol rmsprop_update(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    Symbol data2,
    double lr,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    double gamma1 = 0.95,
    double epsilon = 1e-08,
    double clip_weights = -1.0) {
  SymbolBuilder builder_("rmsprop_update");
  builder_.SetParam("lr", lr);
  builder_.SetParam("wd", wd);
  builder_.SetParam("rescale_grad", rescale_grad);
  builder_.SetParam("clip_gradient", clip_gradient);
  builder_.SetParam("gamma1", gamma1);
  builder_.SetParam("epsilon", epsilon);
  builder_.SetParam("clip_weights", clip_weights);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  builder_.SetInput("data2", data2);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol rmspropalex_update(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    Symbol data2,
    Symbol data3,
    Symbol data4,
    double lr,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    double gamma1 = 0.95,
    double gamma2 = 0.9,
    double epsilon = 1e-08,
    double clip_weights = -1.0) {
  SymbolBuilder builder_("rmspropalex_update");
  builder_.SetParam("lr", lr);
  builder_.SetParam("wd", wd);
  builder_.SetParam("rescale_grad", rescale_grad);
  builder_.SetParam("clip_gradient", clip_gradient);
  builder_.SetParam("gamma1", gamma1);
  builder_.SetParam("gamma2", gamma2);
  builder_.SetParam("epsilon", epsilon);
  builder_.SetParam("clip_weights", clip_weights);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  builder_.SetInput("data2", data2);
  builder_.SetInput("data3", data3);
  builder_.SetInput("data4", data4);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol rnn(const std::string &symbol_name,
    Symbol data,
    Symbol parameters,
    Symbol state,
    Symbol state_cell,
    int state_size,
    int num_layers,
    const std::string &mode,
    bool bidirectional = false,
    double p = 0.0,
    bool state_outputs = false,
    bool lstm_state_clip_nan = false) {
  SymbolBuilder builder_("rnn");
  builder_.SetParam("state_size", state_size);
  builder_.SetParam("num_layers", num_layers);
  builder_.SetParam("mode", mode);
  builder_.SetParam("bidirectional", bidirectional ? 1 : 0);
  builder_.SetParam("p", p);
  builder_.SetParam("state_outputs", state_outputs ? 1 : 0);
  builder_.SetParam("lstm_state_clip_nan", lstm_state_clip_nan ? 1 : 0);
  builder_.SetInput("data", data);
  builder_.SetInput("parameters", parameters);
  builder_.SetInput("state", state);
  builder_.SetInput("state_cell", state_cell);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol roipooling(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    const std::string &pooled_size,
    double spatial_scale) {
  SymbolBuilder builder_("roipooling");
  builder_.SetParam("pooled_size", pooled_size);
  builder_.SetParam("spatial_scale", spatial_scale);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol round(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("round");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol rsqrt(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("rsqrt");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol sample_exponential(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()") {
  SymbolBuilder builder_("sample_exponential");
  builder_.SetParam("shape", shape);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol sample_gamma(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    const std::string &shape = "()") {
  SymbolBuilder builder_("sample_gamma");
  builder_.SetParam("shape", shape);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol sample_generalized_negative_binomial(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    const std::string &shape = "()") {
  SymbolBuilder builder_("sample_generalized_negative_binomial");
  builder_.SetParam("shape", shape);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol sample_multinomial(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()",
    bool get_prob = false,
    const std::string &dtype = "int32") {
  SymbolBuilder builder_("sample_multinomial");
  builder_.SetParam("shape", shape);
  builder_.SetParam("get_prob", get_prob ? 1 : 0);
  builder_.SetParam("dtype", dtype);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol sample_negative_binomial(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    const std::string &shape = "()") {
  SymbolBuilder builder_("sample_negative_binomial");
  builder_.SetParam("shape", shape);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol sample_normal(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    const std::string &shape = "()") {
  SymbolBuilder builder_("sample_normal");
  builder_.SetParam("shape", shape);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol sample_poisson(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()") {
  SymbolBuilder builder_("sample_poisson");
  builder_.SetParam("shape", shape);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol sample_uniform(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    const std::string &shape = "()") {
  SymbolBuilder builder_("sample_uniform");
  builder_.SetParam("shape", shape);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol scatter_nd(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    const std::string &shape) {
  SymbolBuilder builder_("scatter_nd");
  builder_.SetParam("shape", shape);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol sequence_last(const std::string &symbol_name,
    Symbol data,
    Symbol sequence_length,
    bool use_sequence_length = false,
    int axis = 0) {
  SymbolBuilder builder_("sequence_last");
  builder_.SetParam("use_sequence_length", use_sequence_length ? 1 : 0);
  builder_.SetParam("axis", axis);
  builder_.SetInput("data", data);
  builder_.SetInput("sequence_length", sequence_length);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol sequence_mask(const std::string &symbol_name,
    Symbol data,
    Symbol sequence_length,
    bool use_sequence_length = false,
    double value = 0.0,
    int axis = 0) {
  SymbolBuilder builder_("sequence_mask");
  builder_.SetParam("use_sequence_length", use_sequence_length ? 1 : 0);
  builder_.SetParam("value", value);
  builder_.SetParam("axis", axis);
  builder_.SetInput("data", data);
  builder_.SetInput("sequence_length", sequence_length);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol sequence_reverse(const std::string &symbol_name,
    Symbol data,
    Symbol sequence_length,
    bool use_sequence_length = false,
    int axis = 0) {
  SymbolBuilder builder_("sequence_reverse");
  builder_.SetParam("use_sequence_length", use_sequence_length ? 1 : 0);
  builder_.SetParam("axis", axis);
  builder_.SetInput("data", data);
  builder_.SetInput("sequence_length", sequence_length);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol sgd_mom_update(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    Symbol data2,
    double lr,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    double momentum = 0.0) {
  SymbolBuilder builder_("sgd_mom_update");
  builder_.SetParam("lr", lr);
  builder_.SetParam("wd", wd);
  builder_.SetParam("rescale_grad", rescale_grad);
  builder_.SetParam("clip_gradient", clip_gradient);
  builder_.SetParam("momentum", momentum);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  builder_.SetInput("data2", data2);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol sgd_update(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    double lr,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0) {
  SymbolBuilder builder_("sgd_update");
  builder_.SetParam("lr", lr);
  builder_.SetParam("wd", wd);
  builder_.SetParam("rescale_grad", rescale_grad);
  builder_.SetParam("clip_gradient", clip_gradient);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol shape_array(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("shape_array");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol shortconv(const std::string &symbol_name,
    Symbol data,
    Symbol in_proj_weight,
    Symbol conv_weight,
    Symbol out_proj_weight,
    int kernel = 3) {
  SymbolBuilder builder_("shortconv");
  builder_.SetParam("kernel", kernel);
  builder_.SetInput("data", data);
  builder_.SetInput("in_proj_weight", in_proj_weight);
  builder_.SetInput("conv_weight", conv_weight);
  builder_.SetInput("out_proj_weight", out_proj_weight);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol shuffle(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("shuffle");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol sigmoid(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("sigmoid");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol sign(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("sign");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol signsgd_update(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    double lr,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0) {
  SymbolBuilder builder_("signsgd_update");
  builder_.SetParam("lr", lr);
  builder_.SetParam("wd", wd);
  builder_.SetParam("rescale_grad", rescale_grad);
  builder_.SetParam("clip_gradient", clip_gradient);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol signum_update(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    Symbol data2,
    double lr,
    double wd = 0.0,
    double rescale_grad = 1.0,
    double clip_gradient = -1.0,
    double momentum = 0.0,
    double wd_lh = 0.0) {
  SymbolBuilder builder_("signum_update");
  builder_.SetParam("lr", lr);
  builder_.SetParam("wd", wd);
  builder_.SetParam("rescale_grad", rescale_grad);
  builder_.SetParam("clip_gradient", clip_gradient);
  builder_.SetParam("momentum", momentum);
  builder_.SetParam("wd_lh", wd_lh);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  builder_.SetInput("data2", data2);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol sin(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("sin");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol sinh(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("sinh");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol size_array(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("size_array");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol slice(const std::string &symbol_name,
    Symbol data,
    const std::string &begin = "()",
    const std::string &end = "()",
    const std::string &step = "()") {
  SymbolBuilder builder_("slice");
  builder_.SetParam("begin", begin);
  builder_.SetParam("end", end);
  builder_.SetParam("step", step);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol slice_axis(const std::string &symbol_name,
    Symbol data,
    int axis,
    int begin) {
  SymbolBuilder builder_("slice_axis");
  builder_.SetParam("axis", axis);
  builder_.SetParam("begin", begin);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol slice_like(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    const std::string &axes = "()") {
  SymbolBuilder builder_("slice_like");
  builder_.SetParam("axes", axes);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol smooth_l1(const std::string &symbol_name,
    Symbol data,
    double scalar = 1.0) {
  SymbolBuilder builder_("smooth_l1");
  builder_.SetParam("scalar", scalar);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol softmax(const std::string &symbol_name,
    Symbol data,
    int axis = -1) {
  SymbolBuilder builder_("softmax");
  builder_.SetParam("axis", axis);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol softmax_cross_entropy(const std::string &symbol_name,
    Symbol data,
    Symbol label) {
  SymbolBuilder builder_("softmax_cross_entropy");
  builder_.SetInput("data", data);
  builder_.SetInput("label", label);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol softmaxoutput(const std::string &symbol_name,
    Symbol data,
    Symbol label,
    double grad_scale = 1.0,
    double ignore_label = -1.0,
    bool multi_output = false,
    bool use_ignore = false,
    bool preserve_shape = false,
    const std::string &normalization = "null",
    bool out_grad = false,
    double smooth_alpha = 0.0) {
  SymbolBuilder builder_("softmaxoutput");
  builder_.SetParam("grad_scale", grad_scale);
  builder_.SetParam("ignore_label", ignore_label);
  builder_.SetParam("multi_output", multi_output ? 1 : 0);
  builder_.SetParam("use_ignore", use_ignore ? 1 : 0);
  builder_.SetParam("preserve_shape", preserve_shape ? 1 : 0);
  builder_.SetParam("normalization", normalization);
  builder_.SetParam("out_grad", out_grad ? 1 : 0);
  builder_.SetParam("smooth_alpha", smooth_alpha);
  builder_.SetInput("data", data);
  builder_.SetInput("label", label);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol softrelu(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("softrelu");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol softsign(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("softsign");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol sort(const std::string &symbol_name,
    Symbol data,
    const std::string &axis = "(-1)",
    bool is_ascend = true) {
  SymbolBuilder builder_("sort");
  builder_.SetParam("axis", axis);
  builder_.SetParam("is_ascend", is_ascend ? 1 : 0);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol space_to_depth(const std::string &symbol_name,
    Symbol data,
    int block_size) {
  SymbolBuilder builder_("space_to_depth");
  builder_.SetParam("block_size", block_size);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol sparse_retain(const std::string &symbol_name,
    Symbol data0,
    Symbol data1) {
  SymbolBuilder builder_("sparse_retain");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol sparsemoe(const std::string &symbol_name,
    Symbol data,
    Symbol router_weight,
    Symbol expert_bias,
    Symbol expert_gate_weight,
    Symbol expert_up_weight,
    Symbol expert_down_weight,
    Symbol expert_load,
    int num_experts,
    int num_experts_per_tok,
    int num_hidden,
    int num_held = 0,
    int expert_offset = 0,
    const std::string &score = "sigmoid",
    double routed_scaling = 1.0,
    double weight_eps = 1e-06) {
  SymbolBuilder builder_("sparsemoe");
  builder_.SetParam("num_experts", num_experts);
  builder_.SetParam("num_experts_per_tok", num_experts_per_tok);
  builder_.SetParam("num_hidden", num_hidden);
  builder_.SetParam("num_held", num_held);
  builder_.SetParam("expert_offset", expert_offset);
  builder_.SetParam("score", score);
  builder_.SetParam("routed_scaling", routed_scaling);
  builder_.SetParam("weight_eps", weight_eps);
  builder_.SetInput("data", data);
  builder_.SetInput("router_weight", router_weight);
  builder_.SetInput("expert_bias", expert_bias);
  builder_.SetInput("expert_gate_weight", expert_gate_weight);
  builder_.SetInput("expert_up_weight", expert_up_weight);
  builder_.SetInput("expert_down_weight", expert_down_weight);
  builder_.SetInput("expert_load", expert_load);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol spatialtransformer(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    const std::string &target_shape = "(0, 0)",
    const std::string &transform_type = "affine",
    const std::string &sampler_type = "bilinear") {
  SymbolBuilder builder_("spatialtransformer");
  builder_.SetParam("target_shape", target_shape);
  builder_.SetParam("transform_type", transform_type);
  builder_.SetParam("sampler_type", sampler_type);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol split(const std::string &symbol_name,
    Symbol data,
    int num_outputs,
    int axis = 1,
    bool squeeze_axis = false) {
  SymbolBuilder builder_("split");
  builder_.SetParam("num_outputs", num_outputs);
  builder_.SetParam("axis", axis);
  builder_.SetParam("squeeze_axis", squeeze_axis ? 1 : 0);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol sqrt(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("sqrt");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol square(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("square");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol square_sum(const std::string &symbol_name,
    Symbol data,
    bool keepdims = false,
    bool exclude = false) {
  SymbolBuilder builder_("square_sum");
  builder_.SetParam("keepdims", keepdims ? 1 : 0);
  builder_.SetParam("exclude", exclude ? 1 : 0);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol squeeze(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("squeeze");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol stack(const std::string &symbol_name,
    const std::vector<Symbol> &data,
    int axis = 0) {
  SymbolBuilder builder_("stack");
  builder_.SetParam("axis", axis);
  builder_.SetParam("num_args", data.size());
  for (const auto &s : data) builder_.AddInput(s);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol stop_gradient(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("stop_gradient");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol streaming_softmax_ce(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    int axis = -1,
    bool keepdims = false) {
  SymbolBuilder builder_("streaming_softmax_ce");
  builder_.SetParam("axis", axis);
  builder_.SetParam("keepdims", keepdims ? 1 : 0);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol sum(const std::string &symbol_name,
    Symbol data,
    bool keepdims = false,
    bool exclude = false) {
  SymbolBuilder builder_("sum");
  builder_.SetParam("keepdims", keepdims ? 1 : 0);
  builder_.SetParam("exclude", exclude ? 1 : 0);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol sum_axis(const std::string &symbol_name,
    Symbol data,
    bool keepdims = false,
    bool exclude = false) {
  SymbolBuilder builder_("sum_axis");
  builder_.SetParam("keepdims", keepdims ? 1 : 0);
  builder_.SetParam("exclude", exclude ? 1 : 0);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol svmoutput(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    double margin = 1.0,
    double regularization_coefficient = 1.0,
    bool use_linear = false) {
  SymbolBuilder builder_("svmoutput");
  builder_.SetParam("margin", margin);
  builder_.SetParam("regularization_coefficient", regularization_coefficient);
  builder_.SetParam("use_linear", use_linear ? 1 : 0);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol swapaxes(const std::string &symbol_name,
    Symbol data,
    int dim1 = 0,
    int dim2 = 0) {
  SymbolBuilder builder_("swapaxes");
  builder_.SetParam("dim1", dim1);
  builder_.SetParam("dim2", dim2);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol take(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    int axis = 0,
    const std::string &mode = "clip") {
  SymbolBuilder builder_("take");
  builder_.SetParam("axis", axis);
  builder_.SetParam("mode", mode);
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol tan(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("tan");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol tanh(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("tanh");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol tile(const std::string &symbol_name,
    Symbol data,
    const std::string &reps) {
  SymbolBuilder builder_("tile");
  builder_.SetParam("reps", reps);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol to_tensor(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("to_tensor");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol topk(const std::string &symbol_name,
    Symbol data,
    const std::string &axis = "(-1)",
    int k = 1,
    const std::string &ret_typ = "indices",
    bool is_ascend = false,
    const std::string &dtype = "float32") {
  SymbolBuilder builder_("topk");
  builder_.SetParam("axis", axis);
  builder_.SetParam("k", k);
  builder_.SetParam("ret_typ", ret_typ);
  builder_.SetParam("is_ascend", is_ascend ? 1 : 0);
  builder_.SetParam("dtype", dtype);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol transpose(const std::string &symbol_name,
    Symbol data,
    const std::string &axes = "()") {
  SymbolBuilder builder_("transpose");
  builder_.SetParam("axes", axes);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol trunc(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("trunc");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol uniform(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()",
    double low = 0.0,
    double high = 1.0) {
  SymbolBuilder builder_("uniform");
  builder_.SetParam("shape", shape);
  builder_.SetParam("low", low);
  builder_.SetParam("high", high);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol unravel_index(const std::string &symbol_name,
    Symbol data,
    const std::string &shape) {
  SymbolBuilder builder_("unravel_index");
  builder_.SetParam("shape", shape);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol upsampling(const std::string &symbol_name,
    const std::vector<Symbol> &data,
    int scale,
    int num_filter = 0,
    const std::string &sample_type = "nearest",
    const std::string &multi_input_mode = "concat",
    int workspace = 512) {
  SymbolBuilder builder_("upsampling");
  builder_.SetParam("scale", scale);
  builder_.SetParam("num_filter", num_filter);
  builder_.SetParam("sample_type", sample_type);
  builder_.SetParam("multi_input_mode", multi_input_mode);
  builder_.SetParam("workspace", workspace);
  builder_.SetParam("num_args", data.size());
  for (const auto &s : data) builder_.AddInput(s);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol where(const std::string &symbol_name,
    Symbol data0,
    Symbol data1,
    Symbol data2) {
  SymbolBuilder builder_("where");
  builder_.SetInput("data0", data0);
  builder_.SetInput("data1", data1);
  builder_.SetInput("data2", data2);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol zeros(const std::string &symbol_name,
    Symbol data,
    const std::string &shape = "()",
    const std::string &dtype = "float32") {
  SymbolBuilder builder_("zeros");
  builder_.SetParam("shape", shape);
  builder_.SetParam("dtype", dtype);
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

inline Symbol zeros_like(const std::string &symbol_name,
    Symbol data) {
  SymbolBuilder builder_("zeros_like");
  builder_.SetInput("data", data);
  return builder_.CreateSymbol(symbol_name);
}

}  // namespace op
}  // namespace cpp
}  // namespace mxnet

#endif  // MXNET_CPP_OP_H_
